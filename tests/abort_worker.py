"""Worker process for the SIGTERM abort-forensics test.

Launched by tests/test_live_obs.py: runs a real (tiny) `peasoup` CLI
search with the status.json heartbeat enabled, so the parent can wait
for the heartbeat to appear (proof the flight recorder is armed — the
recorder installs before the first snapshot), SIGTERM the run
mid-flight, and assert the forensics: flight.json plus a partial
telemetry manifest marked ``"aborted": true``.

Usage: python abort_worker.py <fil_path> <outdir>
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from peasoup_tpu.utils.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


def main() -> int:
    fil_path, outdir = sys.argv[1], sys.argv[2]
    from peasoup_tpu.cli.peasoup import main as peasoup_main

    return peasoup_main(
        [
            "-i", fil_path,
            "-o", outdir,
            "--dm_end", "40",
            "-n", "2",
            "--limit", "20",
            "--status-json", os.path.join(outdir, "status.json"),
            "--heartbeat-interval", "0.05",
        ]
    )


if __name__ == "__main__":
    sys.exit(main())
