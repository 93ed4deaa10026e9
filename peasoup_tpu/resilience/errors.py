"""The error classification every recovery decision routes through.

Survey pipelines that run unattended for months (the GSP/CRAFTS and
FAST drift-scan operations, arXiv:2110.12749 / 1912.12807) survive by
treating failures as *categories with policies*, not as ad-hoc
try/excepts. peasoup-tpu's scattered recovery code all asked the same
four questions with different heuristics; this module is the single
answer:

- **transient** — flaky I/O (EIO/EAGAIN/short read mid-append), sqlite
  ``database is locked``/``busy`` under WAL contention, filesystem
  races. Policy: bounded retry with backoff
  (:class:`~peasoup_tpu.resilience.policy.RetryPolicy`).
- **resource_exhausted** — device/host out-of-memory (the shrink-retry
  trigger). Policy: descend the degradation ladder
  (:class:`~peasoup_tpu.resilience.policy.DegradationLadder`) — retrying
  the same shape would OOM again.
- **corrupt** — a torn/truncated/garbage artifact (checkpoint, tuning
  cache, baseline). Policy: warn + quarantine the file (``*.corrupt``
  rename) and regenerate
  (:func:`~peasoup_tpu.resilience.policy.load_or_recover`); never
  retry, never crash the run.
- **fatal** — everything else: a programming error or genuinely bad
  input. Policy: raise; the campaign layer's attempt budget +
  quarantine is the recovery.

Exception *types* alone cannot classify (jaxlib raises one runtime
error type for every status code; OSError spans flaky and fatal), so
classification reads errno/message contracts pinned by tests
(tests/test_aux.py pins the real JAX OOM signature).
"""

from __future__ import annotations

import errno as _errno
import json

TRANSIENT = "transient"
RESOURCE_EXHAUSTED = "resource_exhausted"
CORRUPT = "corrupt"
FATAL = "fatal"


class TransientIOError(OSError):
    """An explicitly-transient I/O failure (short read of a growing
    file, injected flaky read). Always classified TRANSIENT."""


class CorruptArtifactError(Exception):
    """A loader detected a torn/invalid artifact. Always CORRUPT."""


class WorkerKilled(BaseException):
    """Simulated SIGKILL for fault injection: derives from
    BaseException so no ``except Exception`` recovery path can observe
    it — exactly like a real kill, the claim is NOT released and the
    lease reaper is the only recovery."""


# errnos that indicate a retryable filesystem/network hiccup rather
# than a broken program or a genuinely missing resource
_TRANSIENT_ERRNOS = frozenset(
    x
    for x in (
        _errno.EIO,
        _errno.EAGAIN,
        _errno.EINTR,
        _errno.EBUSY,
        _errno.ETIMEDOUT,
        getattr(_errno, "ESTALE", None),  # NFS handle expiry
        getattr(_errno, "ECONNRESET", None),
    )
    if x is not None
)

_CORRUPT_TYPES = (
    json.JSONDecodeError,
    EOFError,
    UnicodeDecodeError,
)


def is_resource_exhausted(exc: BaseException) -> bool:
    """Device or host out-of-memory signature (XLA compile- or
    run-time). jaxlib exposes no status-code attribute on its runtime
    error, so the typed contract available is: a JaxRuntimeError whose
    ABSL status message LEADS with the canonical code
    RESOURCE_EXHAUSTED (absl::Status string formatting — stabler than
    substring-anywhere). Host allocation failure (MemoryError) joins
    it; the substring heuristics remain only as a fallback for
    wrapped/re-raised text."""
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc)
    try:
        import jax

        if isinstance(
            exc, jax.errors.JaxRuntimeError
        ) and msg.lstrip().startswith("RESOURCE_EXHAUSTED"):
            return True
    except Exception:
        pass  # no jax: fall through to the text heuristics
    return "RESOURCE_EXHAUSTED" in msg or (
        "memory" in msg.lower() and "hbm" in msg.lower()
    )


def _is_sqlite_contention(exc: BaseException) -> bool:
    try:
        import sqlite3
    except Exception:
        return False
    if not isinstance(exc, sqlite3.OperationalError):
        return False
    msg = str(exc).lower()
    return "locked" in msg or "busy" in msg


def is_corrupt(exc: BaseException) -> bool:
    if isinstance(exc, CorruptArtifactError):
        return True
    if isinstance(exc, _CORRUPT_TYPES):
        return True
    # zipfile/np.load damage without importing zipfile eagerly
    name = type(exc).__name__
    if name in ("BadZipFile", "BadZipfile", "UnpicklingError"):
        return True
    try:
        from ..obs.schema import SchemaError

        if isinstance(exc, SchemaError):
            return True
    except Exception:
        pass
    return False


def is_transient(exc: BaseException) -> bool:
    if isinstance(exc, TransientIOError):
        return True
    if _is_sqlite_contention(exc):
        return True
    if isinstance(exc, (FileNotFoundError, PermissionError)):
        # ENOENT/EACCES are protocol states (a racing rename, a claim
        # already taken), not hiccups — call sites handle them
        return False
    if isinstance(exc, TimeoutError):  # OSError subclass: check first
        return True
    if isinstance(exc, OSError):
        return exc.errno in _TRANSIENT_ERRNOS
    return False


def classify(exc: BaseException) -> str:
    """Map an exception to its error class. Order matters: the
    resource_exhausted check runs first because jax wraps OOM in the
    same type it uses for everything else."""
    if is_resource_exhausted(exc):
        return RESOURCE_EXHAUSTED
    if is_transient(exc):
        return TRANSIENT
    if is_corrupt(exc):
        return CORRUPT
    return FATAL
