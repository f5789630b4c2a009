"""apex_tpu.lint -- project-invariant linter + jaxpr-level hazard analyzers.

The repo's hardest-won correctness and performance invariants used to be
enforced by hand: CLAUDE.md prose (never differentiate a bare
``lax.psum``/``pmean`` of the loss; the T(8,128) lane-padding tax) plus
one ad-hoc AST
walker inside tests/test_diagnose.py. veScale-style SPMD stacks (PAPERS.md,
arxiv 2509.07003) and the cross-replica weight-update sharding work (arxiv
2004.13336) both argue for MECHANICAL consistency checking of
sharding/collective structure; this package is that check, run before a
multi-hour TPU job instead of during its postmortem.

Three engines:

- **Engine 1 -- source AST rules** (:mod:`rules_source`, CLI
  ``python -m apex_tpu.lint [--strict] [--format json]``): walks
  ``apex_tpu/`` + ``examples/`` + ``benchmarks/`` and enforces the named,
  individually suppressable rules (``comm-scope``, ``grad-collective``,
  ``pallas-interpret``, ``module-citation``, ``exception-retention``). Wired into tier-1 as tests/test_lint.py: the
  repo must lint clean, every suppression justified.
- **Engine 2 -- jaxpr/trace analyzers** (:mod:`trace`): hazards XLA
  compiles without complaint -- :func:`trace.lane_padding_report` (bytes
  lost to T(8,128) minor-dim padding), :func:`trace.transpose_hazards`
  (a collective of the loss inside the differentiated region, found as an
  extra scalar psum in the backward jaxpr),
  :func:`trace.recompile_hazards` (weak-type / python-scalar signature
  churn), and :func:`trace.sequence_parallel_hazards` (a psum of
  activations on the TP axis inside a sequence-parallel forward -- the
  psum_scatter/all_gather decomposition silently regressed). Wired into
  ``monitor.selftest`` and the ``benchmarks/gpt_scaling.py`` per-config
  report. All of engine 2 runs on engine 3's shared single-trace walker.
- **Engine 3 -- whole-program IR passes** (:mod:`ir` + :mod:`passes`,
  gate CLI ``python -m apex_tpu.lint.audit``): one ``jax.make_jaxpr``
  trace, one recursive walk threading shard_map mesh/axis context, remat
  containment, cond-branch position, and lazy source provenance
  (:class:`ir.StepIR`); registered passes
  (``collective-consistency``, ``static-hbm``, ``dtype-drift``,
  ``comm-bytes``) share the walk via :func:`ir.run_passes`, and findings
  are waived at their provenance line with the same
  ``# lint: disable=<rule> -- why`` grammar. The audit gate runs every
  pass over the canonical step programs (dense, zero, zero3+prefetch,
  zerobubble, serve prefill/decode) off-TPU and emits one JSON verdict
  line; wired into ``monitor.selftest`` and ``dryrun_multichip``.

No reference-file citation: the reference (NVIDIA Apex) ships no static
analysis; the rule set encodes this repo's own conventions (CLAUDE.md,
parallel/collectives.py:20-24, ops/flash_attention.py lane-padding notes).
"""

from apex_tpu.lint.findings import Finding, LintReport, Suppressions  # noqa: F401
from apex_tpu.lint.ir import (  # noqa: F401
    PASS_REGISTRY,
    StepIR,
    register_pass,
    run_passes as run_ir_passes,
    trace_ir,
)
from apex_tpu.lint.rules_source import (  # noqa: F401
    RULES,
    comm_scope_check,
    repo_root,
    run_paths,
)
