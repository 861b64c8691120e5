"""GBDT forest serving: raw-float requests -> binned -> fused traversal.

The inference half of the paper's system: the parameter server trains a
forest (``repro.ps``), checkpoints its ``TrainState``, and this module
serves it. Contracts (DESIGN.md §6a, §17):

- **Wave batching** — the queue pattern of ``serving.engine``: variable-size
  prediction requests (each a block of rows) are packed row-wise into
  fixed-capacity waves of ``max_rows`` and padded to ONE static shape, so
  every wave hits the same jitted predict and there is exactly one compile.
  Requests larger than ``max_rows`` are split into sub-waves internally and
  reassembled under the original uid — callers never see the wave geometry.
- **Serve-time binning** — requests carry *raw float* features; the jitted
  predict applies the training-time quantile edges (``BinnedData.bin_edges``
  via ``trees.binning.apply_bins``) before traversal, so serving sees
  exactly the bins training saw.
- **Hot swap** — the server polls the checkpoint directory for a newer step
  and swaps the forest atomically (the forest is a jit *argument*, not a
  captured constant, so a swap is just a new pytree with the same shapes:
  zero retrace, zero downtime). Swap lag is bounded: ``maybe_reload`` runs
  every ``reload_every_waves`` waves from the serving path itself, and
  ``start_reload_poller`` adds a wall-clock-bounded background poller for
  idle servers.
- **Quantized serving** — ``quantize='int8'|'fp16'`` installs
  ``Forest.quantize`` payloads (checkpoint reloads re-quantize on install);
  scores stay within ``trees.quantization_atol`` of the f32 forest's.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import pathlib
import threading
import time
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint, obs
from repro.kernels import ops
from repro.objectives import Objective, get_objective
from repro.trees.binning import apply_bins
from repro.trees.forest import Forest

_FOREST_FIELDS = ("feature", "threshold", "leaf_value", "n_trees", "base_score")


def _nonfinite_rows(x: np.ndarray) -> np.ndarray:
    """Indices of rows containing any NaN/±inf feature."""
    return np.flatnonzero(~np.isfinite(x).all(axis=1))


def load_forest_checkpoint(
    root: str | pathlib.Path, step: int, like: Forest | None = None
) -> Forest:
    """Restore a ``Forest`` from a checkpoint written by the training loop.

    Works on both bare-``Forest`` checkpoints (leaf paths ``.feature`` ...)
    and full ``TrainState`` checkpoints (``.forest/.feature`` ...), so the
    server never needs the training-set-sized ``f`` vector to rebuild its
    template. Leaves are matched by trailing field name; when several
    leaves end in the same field (a state with both ``forest`` and, say, an
    EMA ``shadow_forest``), the one whose *parent* segment is ``forest`` is
    preferred, and anything still ambiguous raises instead of silently
    picking manifest order. With ``like``, shapes are validated against the
    serving template (capacity and depth are static for the jit cache).
    """
    d = checkpoint.step_dir(root, step)
    manifest = json.loads((d / "manifest.json").read_text())
    candidates: dict[str, list[tuple[list[str], dict]]] = {
        f: [] for f in _FOREST_FIELDS
    }
    for entry in manifest["leaves"]:
        # Path segments come from tree_flatten_with_path: ".forest" for
        # attributes, "['forest']" for dict keys — normalize both.
        segs = [s.strip(".[]'\"") for s in entry["path"].split("/")]
        if segs[-1] in candidates:
            candidates[segs[-1]].append((segs, entry))
    found: dict[str, np.ndarray] = {}
    for field, cands in candidates.items():
        if len(cands) > 1:
            preferred = [c for c in cands if len(c[0]) > 1 and c[0][-2] == "forest"]
            if len(preferred) != 1:
                paths = sorted(e["path"] for _, e in cands)
                raise KeyError(
                    f"checkpoint {d}: forest leaf {field!r} is ambiguous — "
                    f"{len(cands)} leaves end in it ({paths}) and "
                    f"{'none' if not preferred else 'several'} sit under a "
                    "'forest' parent"
                )
            cands = preferred
        if cands:
            found[field] = np.load(d / cands[0][1]["file"])
    missing = [f for f in _FOREST_FIELDS if f not in found]
    if missing:
        raise KeyError(f"checkpoint {d} has no forest leaves {missing}")
    forest = Forest(
        feature=jnp.asarray(found["feature"], jnp.int32),
        threshold=jnp.asarray(found["threshold"], jnp.int32),
        leaf_value=jnp.asarray(found["leaf_value"], jnp.float32),
        n_trees=jnp.asarray(found["n_trees"], jnp.int32),
        base_score=jnp.asarray(found["base_score"], jnp.float32),
    )
    if like is not None:
        for name in ("feature", "threshold", "leaf_value", "base_score"):
            got = getattr(forest, name).shape
            want = getattr(like, name).shape
            if got != want:
                raise ValueError(
                    f"{name}: checkpoint shape {got} != serving template {want}"
                )
    return forest


@dataclasses.dataclass
class PredictRequest:
    uid: int
    x: np.ndarray  # (n, F) float32 — raw (unbinned) feature rows
    # Engine routing (serving.continuous): pin this request to a named
    # forest version; None lets the engine's A/B weights route it.
    version: str | None = None


@dataclasses.dataclass
class PredictResult:
    uid: int
    scores: np.ndarray  # (n,) raw margins — or (n, K) linked predictions
    model_step: int  # checkpoint step that served this request
    latency_s: float  # end-to-end: queue_s + compute_s
    queue_s: float = 0.0  # arrival -> first sub-wave starts computing
    compute_s: float = 0.0  # summed wave compute across this uid's sub-waves
    # Forest version that served this request (set by the continuous
    # engine; a bare ForestServer leaves it None).
    version: str | None = None
    # Row indices (within the request) that contained NaN/±inf features;
    # empty when the request was clean. Only populated in 'flag' mode —
    # 'reject' mode never admits such a request.
    nonfinite_rows: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64)
    )


@dataclasses.dataclass
class _Assembly:
    """Per-request reassembly state for chunked (multi-part) requests.

    All mutable fields are touched only under the server's ``_qlock`` —
    parts of one request can ride waves run by different threads.
    """

    req: PredictRequest
    x: np.ndarray  # validated float32 copy of req.x
    arrival_s: float  # stamped in submit(), before any queueing
    parts_left: int
    scores: np.ndarray | None = None
    model_step: int = -1
    queue_s: float = -1.0  # < 0 until the first part starts computing
    compute_s: float = 0.0


@dataclasses.dataclass
class _Part:
    asm: _Assembly
    lo: int  # row slice [lo, hi) of asm.x this part carries
    hi: int


class ForestServer:
    """Wave-batched GBDT inference with checkpoint hot-swap.

    ``forest`` is the serving template (its capacity/depth/output count fix
    the jit shapes); ``bin_edges`` are the training-time quantile edges.
    With ``ckpt_root``, ``maybe_reload`` polls ``checkpoint.latest_step``
    and swaps in newer forests; the serving path calls it every
    ``reload_every_waves`` waves so swap lag is bounded in waves, and
    ``start_reload_poller`` bounds it in wall-clock for idle servers.

    With ``objective`` (an ``Objective`` or registry spec string), the
    objective's ``link`` is applied INSIDE the jitted predict — served
    outputs are probabilities/scores with exactly the training-time
    semantics (e.g. (rows, K) softmax rows for ``"multiclass:K"``).
    Without it, raw F(x) margins are served (the historical contract).

    With ``quantize`` ('int8' or 'fp16'), the installed forest — initial
    and every hot-swapped reload — is packed via ``Forest.quantize``; the
    f32 template is kept for checkpoint shape validation. Served scores
    stay within ``trees.quantization_atol`` of the f32 scores.

    Non-finite requests (``on_nonfinite``): training never sees NaN/±inf,
    so at serve time they are malformed input, not data. ``"reject"``
    (default) refuses the request in ``submit``; ``"flag"`` serves it —
    ``apply_bins`` clamps ±inf and routes NaN to its deterministic NaN bin
    — and reports the offending row indices in
    ``PredictResult.nonfinite_rows`` so the caller can discount them.

    Thread discipline (repro.analysis.locks): the hot-swap pair
    (``forest``/``model_step``) and the wave counter live under ``_lock``;
    the part queue and reassembly state live under ``_qlock``. The two are
    never held together.
    """

    def __init__(
        self,
        forest: Forest,
        bin_edges: jax.Array,
        *,
        ckpt_root: str | pathlib.Path | None = None,
        max_rows: int = 256,
        backend: str = "auto",
        model_step: int = -1,
        objective: Objective | str | None = None,
        on_nonfinite: str = "reject",
        reload_every_waves: int = 8,
        quantize: str | None = None,
    ):
        if on_nonfinite not in ("reject", "flag"):
            raise ValueError(
                f"on_nonfinite must be 'reject' or 'flag', got {on_nonfinite!r}"
            )
        if reload_every_waves < 1:
            raise ValueError("reload_every_waves must be >= 1")
        # The hot-swap pair must move together: a wave served with the new
        # forest but the old step (or vice versa) mislabels results. Both
        # live under `_lock`; repro.analysis.locks checks the discipline.
        self._lock = threading.Lock()
        # Queue + reassembly state: submit/wave threads race on these.
        self._qlock = threading.Lock()
        self._template = forest  # f32 template for checkpoint validation
        self._quantize = quantize
        packed = forest.quantize(quantize) if quantize else forest
        self.forest = packed  # guarded-by: self._lock
        self.bin_edges = jnp.asarray(bin_edges, jnp.float32)
        self.ckpt_root = ckpt_root
        self.max_rows = max_rows
        self.model_step = model_step  # guarded-by: self._lock
        self.on_nonfinite = on_nonfinite
        self.reload_every_waves = reload_every_waves
        self.waves_served = 0  # guarded-by: self._lock
        self.objective = get_objective(objective) if objective is not None else None
        depth = forest.depth
        n_outputs = forest.n_outputs
        obj = self.objective
        if obj is not None and obj.n_outputs != n_outputs:
            # A mismatched link would silently normalize across the wave
            # (e.g. softmax over a (rows,) vector) instead of per row.
            raise ValueError(
                f"objective {obj.name!r} has {obj.n_outputs} outputs but the "
                f"forest serves {n_outputs}"
            )

        def predict(forest, edges: jax.Array, x: jax.Array) -> jax.Array:
            bins = apply_bins(x, edges)
            pred = ops.forest_traverse(
                bins, forest.feature, forest.threshold, forest.leaf_value,
                forest.n_trees, depth, backend=backend, n_outputs=n_outputs,
                leaf_scale=getattr(forest, "leaf_scale", None),
            )
            raw = forest.base_score + pred
            return raw if obj is None else obj.link(raw)

        self._predict = jax.jit(predict)
        self._queue: collections.deque[_Part] = collections.deque()  # guarded-by: self._qlock
        self._poller: threading.Thread | None = None
        self._poll_stop: threading.Event | None = None

    def submit(self, req: PredictRequest) -> None:  # concurrent
        """Validate and enqueue. Requests wider than ``max_rows`` are split
        into sub-waves here and reassembled under the original uid; arrival
        is stamped NOW, so reported ``queue_s`` includes every second the
        request sits behind earlier traffic (arrival is the start of the
        ``serve.submit`` span)."""
        with obs.timed("serve.submit", uid=req.uid) as admit:
            x = np.asarray(req.x, np.float32)
            if x.ndim != 2 or x.shape[1] != self.bin_edges.shape[0]:
                raise ValueError(
                    f"request {req.uid}: expected (n, {self.bin_edges.shape[0]}) "
                    f"features, got {x.shape}"
                )
            bad = _nonfinite_rows(x)
            if bad.size and self.on_nonfinite == "reject":
                raise ValueError(
                    f"request {req.uid}: non-finite features in rows "
                    f"{bad.tolist()} (server runs on_nonfinite='reject'; "
                    f"use 'flag' to serve them with clamped/NaN-routed bins)"
                )
            n = x.shape[0]
            cuts = list(range(0, n, self.max_rows)) or [0]
            asm = _Assembly(req=req, x=x, arrival_s=admit.t0, parts_left=len(cuts))
            # All parts land under ONE lock acquisition: a draining wave thread
            # can never observe a half-enqueued request (drain completeness).
            with self._qlock:
                for lo in cuts:
                    self._queue.append(_Part(asm, lo, min(lo + self.max_rows, n)))

    # ------------------------------------------------------------------ waves
    def queued_rows(self) -> int:  # concurrent
        """Rows currently waiting (the engine's fill-cut signal)."""
        with self._qlock:
            return sum(p.hi - p.lo for p in self._queue)

    def oldest_wait(self, now: float | None = None) -> float:  # concurrent
        """Seconds the head-of-line request has waited; 0.0 when idle.
        The engine cuts a wave when this approaches the latency SLO."""
        if now is None:
            now = time.perf_counter()
        with self._qlock:
            if not self._queue:
                return 0.0
            return now - self._queue[0].asm.arrival_s

    def _next_wave(self) -> list[_Part]:  # concurrent
        """Pop queued parts while their rows fit in one ``max_rows`` wave."""
        with self._qlock:
            wave, rows = [], 0
            while self._queue and rows + (
                self._queue[0].hi - self._queue[0].lo
            ) <= self.max_rows:
                part = self._queue.popleft()
                wave.append(part)
                rows += part.hi - part.lo
            return wave

    def serve_next_wave(self) -> list[PredictResult]:  # concurrent
        """Cut and serve one wave; returns results for every request whose
        LAST part rode it (requests still missing parts stay pending)."""
        wave = self._next_wave()
        return self._run_wave(wave) if wave else []

    def _run_wave(self, wave: list[_Part]) -> list[PredictResult]:  # concurrent
        """Serve one cut wave inside a ``serve.wave`` span: ``pack`` (copy
        and pad to ``max_rows``), ``run`` (put, predict, wait), ``fetch``
        (copy back), ``assemble`` (results). A request's ``compute_s`` runs
        from the start of ``run`` to the end of ``fetch``."""
        sizes = [p.hi - p.lo for p in wave]
        n_rows = sum(sizes)
        with obs.span("serve.wave", rows=n_rows, pad=self.max_rows - n_rows):
            with obs.span("serve.wave.pack"):
                rows = np.zeros((self.max_rows, self.bin_edges.shape[0]), np.float32)
                if n_rows:
                    rows[:n_rows] = np.concatenate(
                        [p.asm.x[p.lo : p.hi] for p in wave], axis=0
                    )
            obs.count("serve.rows", n_rows)
            obs.count("serve.pad_rows", self.max_rows - n_rows)
            # One consistent snapshot of the swap pair: every result in this
            # wave is labeled with the step of the forest that computed it,
            # even if a poller thread swaps mid-wave.
            with self._lock:
                forest, model_step = self.forest, self.model_step
            with obs.timed("serve.wave.run") as run:
                scores = self._predict(forest, self.bin_edges, jnp.asarray(rows))
                jax.block_until_ready(scores)
            with obs.timed("serve.wave.fetch") as fetch:
                scores = np.asarray(scores)
            t0, dt = run.t0, fetch.t1 - run.t0
            with self._lock:
                self.waves_served += 1
                waves = self.waves_served
            with obs.span("serve.wave.assemble"):
                results = self._assemble(wave, sizes, scores, t0, dt, model_step)
            if waves % self.reload_every_waves == 0:
                # Bounded-lag hot swap: the serving path itself polls, so a
                # busy server can never fall more than reload_every_waves
                # waves behind the newest checkpoint.
                self.maybe_reload()
        return results

    def _assemble(self, wave, sizes, scores, t0, dt, model_step):  # concurrent
        """Scatter a wave's scores into its requests; the results of every
        request whose last part rode it."""
        results, off = [], 0
        for part, n in zip(wave, sizes):
            asm = part.asm
            with self._qlock:
                if asm.scores is None:
                    asm.scores = np.zeros(
                        (asm.x.shape[0],) + scores.shape[1:], scores.dtype
                    )
                if asm.queue_s < 0:
                    asm.queue_s = t0 - asm.arrival_s
                asm.scores[part.lo : part.hi] = scores[off : off + n]
                asm.compute_s += dt
                # max, not last: with concurrent wave threads, "the step
                # that served this request" is the newest forest any of
                # its parts saw.
                asm.model_step = max(asm.model_step, model_step)
                asm.parts_left -= 1
                if asm.parts_left == 0:
                    results.append(
                        PredictResult(
                            uid=asm.req.uid,
                            scores=asm.scores,
                            model_step=asm.model_step,
                            latency_s=asm.queue_s + asm.compute_s,
                            queue_s=asm.queue_s,
                            compute_s=asm.compute_s,
                            # Recomputed on the FULL request at assembly
                            # time (cheap) — indices are request-relative
                            # regardless of how the rows were chunked.
                            nonfinite_rows=_nonfinite_rows(asm.x),
                        )
                    )
            off += n
        return results

    # --------------------------------------------------------------- hot swap
    def maybe_reload(self) -> bool:  # concurrent
        """Swap in the newest checkpointed forest, if any. Zero-downtime:
        shapes are static, so the next wave just sees the new pytree.
        Safe from a poller thread: the (slow) checkpoint load happens
        outside the lock, then compare-and-swap — a racing reloader that
        already installed this step or newer wins."""
        if self.ckpt_root is None:
            return False
        step = checkpoint.latest_step(self.ckpt_root)
        with self._lock:
            current = self.model_step
        if step is None or step <= current:
            return False
        forest = load_forest_checkpoint(self.ckpt_root, step, like=self._template)
        if self._quantize:
            forest = forest.quantize(self._quantize)
        with self._lock:
            if step <= self.model_step:
                return False
            self.forest = forest
            self.model_step = step
        return True

    def start_reload_poller(self, interval_s: float = 0.05) -> None:
        """Wall-clock-bounded hot swap: a daemon thread polls the
        checkpoint root every ``interval_s`` even when no waves are being
        served, so swap lag is bounded for idle/bursty servers too."""
        if self._poller is not None:
            return
        stop = threading.Event()

        def _poll():  # concurrent
            while not stop.wait(interval_s):
                self.maybe_reload()

        self._poll_stop = stop
        self._poller = threading.Thread(
            target=_poll, name="forest-reload-poller", daemon=True
        )
        self._poller.start()

    def stop_reload_poller(self) -> None:
        if self._poller is None:
            return
        assert self._poll_stop is not None
        self._poll_stop.set()
        self._poller.join()
        self._poller = None
        self._poll_stop = None

    def run(
        self, requests: Iterable[PredictRequest] | None = None
    ) -> list[PredictResult]:
        for r in requests or ():
            self.submit(r)
        done: list[PredictResult] = []
        while True:
            self.maybe_reload()
            wave = self._next_wave()
            if not wave:
                break  # parts never exceed max_rows: empty wave == drained
            done.extend(self._run_wave(wave))
        return sorted(done, key=lambda r: r.uid)
