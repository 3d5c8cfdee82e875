"""graft-lint: project-specific static analysis over the package AST.

Mechanizes the cross-cutting contracts every PR has hand-enforced
since PR 1 (stdlib ``ast`` only — no new dependencies):

- ``jit_stability`` — the zero-steady-state-recompile contract's static
                      half: no Python-value branching on traced args
                      inside jit/shard_map-reachable functions, no
                      jit construction in loop bodies, no dispatch
                      shapes built from raw (non-pow2-bucketed)
                      request lengths.
- ``host_sync``     — no implicit device->host syncs (``int()`` /
                      ``float()`` / ``bool()`` / ``.item()`` /
                      ``np.asarray`` on jitted-call results) in the
                      serving hot loop, except sites allowlisted with
                      ``# graft-lint: sync-ok(<reason>)``.
- ``locks``         — the ``_GUARDED_BY`` declaration convention: every
                      access to a guarded attribute is lexically inside
                      ``with self._lock`` (the PR 7 sticky-map race
                      class, caught at lint time).
- ``names``         — pyflakes-style undefined-name / unused-import
                      sweep over the whole package.

Run it: ``python -m mpi_tensorflow_tpu.analysis`` (see
``analysis/runner.py`` and docs/ANALYSIS.md).  Tier-1 runs it on the live
tree: ``tests/test_analysis.py::test_live_repo_scans_clean``.
"""

from mpi_tensorflow_tpu.analysis.core import Finding  # noqa: F401
