"""repro.analysis — static verification of StitchIR artifacts.

Zero-jax: every pass checks artifacts (graphs, fusion plans, disk plan
records, KV allocator snapshots) *without executing them*, emitting
structured :class:`Finding` records with stable ``RA0xx`` codes instead
of raising mid-pipeline.  See :mod:`repro.analysis.findings` for the
code registry.

Passes:
  * :func:`verify_graph`   — IR legality (SSA, shapes, dtypes, dead code)
  * :func:`verify_plan`    — fusion-plan legality (cover, cycles, scratch,
    no custom kernel in a fused group); :func:`verify_record` for disk records,
    :func:`verify_compiled` for compiled artifacts
  * :func:`check_donation` — donation/aliasing hazards
  * :func:`audit_kv`       — paged-KV refcount conservation over a
    :func:`snapshot`

Wired in at: ``StitchCompiler(verify=...)`` (refuses ERROR plans),
``repro.cache`` replay (demotes bad records to a miss),
``Engine(debug_kv=True)`` (asserts clean audits on release/drain), and
``python -m repro.analysis`` / ``launch/inspect.py verify`` offline.
"""

from .alias import check_donation
from .findings import (CODES, ERROR, WARN, Finding, VerificationError,
                       errors, format_findings, summarize, warnings_)
from .kvaudit import KVSnapshot, audit_kv, snapshot
from .plan import GroupView, verify_compiled, verify_plan, verify_record
from .verify import verify_graph

__all__ = [
    "Finding", "VerificationError", "CODES", "ERROR", "WARN",
    "errors", "warnings_", "summarize", "format_findings",
    "verify_graph",
    "GroupView", "verify_plan", "verify_record", "verify_compiled",
    "check_donation",
    "KVSnapshot", "snapshot", "audit_kv",
]
