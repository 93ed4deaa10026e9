"""Unified resilience layer: error classification, retry/degradation policy,
corrupt-artifact recovery, deterministic fault injection, and the
process-global ``resilience`` status accounting.

See README "Resilience & chaos testing". The pieces:

- :mod:`~peasoup_tpu.resilience.errors` — transient /
  resource_exhausted / corrupt / fatal classification.
- :mod:`~peasoup_tpu.resilience.policy` — :class:`RetryPolicy`,
  :class:`DegradationLadder`, :func:`load_or_recover`,
  :func:`guard_thread`.
- :mod:`~peasoup_tpu.resilience.faults` — named fault sites driven by
  a seeded ``PEASOUP_FAULTS`` schedule (zero overhead when disabled).
- :mod:`~peasoup_tpu.resilience.stats` — the counters behind the
  ``resilience`` section in status.json and the telemetry manifest.

The chaos soak that exercises all of it end-to-end lives in
:mod:`peasoup_tpu.tools.chaos` (``peasoup-chaos``).
"""

from . import faults
from .errors import (
    CORRUPT,
    FATAL,
    RESOURCE_EXHAUSTED,
    TRANSIENT,
    CorruptArtifactError,
    TransientIOError,
    WorkerKilled,
    classify,
    is_corrupt,
    is_resource_exhausted,
    is_transient,
)
from .policy import (
    DB_RETRY,
    IO_RETRY,
    DegradationLadder,
    RetryPolicy,
    guard_thread,
    load_or_recover,
    quarantine_artifact,
)
from .revoke import (
    RevokeToken,
    SearchPreempted,
    activate_token,
    check_revoke,
    current_token,
)
from .stats import STATS

__all__ = [
    "RevokeToken",
    "SearchPreempted",
    "activate_token",
    "check_revoke",
    "current_token",
    "CORRUPT",
    "FATAL",
    "RESOURCE_EXHAUSTED",
    "TRANSIENT",
    "CorruptArtifactError",
    "TransientIOError",
    "WorkerKilled",
    "classify",
    "is_corrupt",
    "is_resource_exhausted",
    "is_transient",
    "DB_RETRY",
    "IO_RETRY",
    "DegradationLadder",
    "RetryPolicy",
    "guard_thread",
    "load_or_recover",
    "quarantine_artifact",
    "STATS",
    "faults",
]
