"""repro.lint: static analysis over the repo's lowered jax programs and
host serving state.

The paper's claim is structural — merged programs must CONTAIN no Q/P
matmul — and the serving stack's worst shipped bugs (zero-copy numpy
aliasing, worst-case buffer regressions, silently-dropped donation) are
all properties of the program or the host/device boundary, checkable
before a single token is decoded.  This package checks them:

  walker      shared jaxpr IR traversal (scan/cond/pjit/pallas bodies)
  rules       Finding / LintTarget / LintRule + the rule registry
  builtin     the built-in rules (NoForbiddenMatmul, NoOversizedBuffer,
              DonationEffective, NoPerLayerPoolSlice,
              NoDtypePromotionDrift, NoHostTransferInStepLoop,
              NoDequantizedPoolBuffer)
  sweep       sweep() — lint EVERY registered (cache_kind, style, impl)
              decode/prefill/chunk backend combo, zero per-combo code
  aliasing    audit_engine() — the host-aliasing race detector
  submitpath  audit_submit_path() — NoSyncPrefillInSubmit: the scheduled
              engine's submit must enqueue only (with positive control)
  retention   audit_retention() — NoWriteIntoHeldPage: no write path may
              mutate a page a peer or the prefix tree still holds
  report      human/JSON rendering (tools/jaxlint.py is the CLI)
"""
from repro.lint import aliasing, report, retention, submitpath, walker  # noqa: F401,E501
from repro.lint.builtin import (BUILTIN_RULES, DonationEffective,  # noqa: F401
                                NoDequantizedPoolBuffer,
                                NoDtypePromotionDrift, NoForbiddenMatmul,
                                NoHostTransferInObsHooks,
                                NoHostTransferInStepLoop, NoOversizedBuffer,
                                NoPerLayerPoolSlice)
from repro.lint.rules import (Finding, LintRule, LintTarget,  # noqa: F401
                              all_rules, get_rule, register_rule,
                              registered_rules, run_rules)
from repro.lint.sweep import (SweepReport, TargetReport,  # noqa: F401
                              register_sweep_builders, sweep, sweep_models)
from repro.lint.aliasing import audit_engine  # noqa: F401
from repro.lint.retention import audit_retention  # noqa: F401
from repro.lint.submitpath import audit_submit_path  # noqa: F401
