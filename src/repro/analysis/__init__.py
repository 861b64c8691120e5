"""repro.analysis — machine-checked invariants (DESIGN.md §15).

Four checkers turn the repo's hand-enforced rules into a CI gate:

  determinism — trace the real jaxprs of ``engine.round_body`` /
      ``propose_tree`` / ``server_fold`` / ``staleness_scale`` and the
      sharded builder; flag FMA-contractible seam crossings that bypass
      the ``optimization_barrier``, f64 double-rounding of constants, and
      non-additive combines of local×aggregated values before a ``psum``
      (the subtract-after-psum invariant).
  locks — ``# guarded-by:`` lock-discipline AST pass over the threaded
      runtime and the serving hot-swap pair.
  vmem — BlockSpec scalar/SMEM placement in the kernel modules.
  lints — hardcoded ``interpret=True``, stray ``PRNGKey`` minting outside
      the ticket-key derivation sites, unknown trace-v2 row fields.

Entry point: ``PYTHONPATH=src python -m repro.analysis`` (see
``repro.analysis.cli``). This module — and every checker except
``determinism`` — imports no jax, so the lint tier can run it on a bare
interpreter.
"""
from repro.analysis.findings import Finding  # noqa: F401

__all__ = ["Finding"]
