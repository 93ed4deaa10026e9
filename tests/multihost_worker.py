"""Worker process for the real 2-process multi-host test.

Launched by tests/test_multihost.py with JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID in the env: initialises
jax.distributed over CPU (4 virtual devices per process), runs the
multi-host search driver (parallel/multihost.py:run_search) on the
given filterbank, and dumps the finalized candidate list so the parent
can compare it bitwise against a single-process run.

Usage: python multihost_worker.py <fil_path> <out_pickle> <cfg_json>
(cfg_json = JSON dict of SearchConfig fields — single source of truth
lives in the launching test)
"""

import json
import os
import pickle
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from peasoup_tpu.utils.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


def main() -> int:
    fil_path, out_path = sys.argv[1], sys.argv[2]
    cfg_fields = json.loads(sys.argv[3]) if len(sys.argv) > 3 else {}

    from peasoup_tpu.io import read_filterbank
    from peasoup_tpu.parallel import multihost
    from peasoup_tpu.pipeline import SearchConfig

    fil = read_filterbank(fil_path)
    cfg = SearchConfig(**cfg_fields)
    res = multihost.run_search(fil, cfg)
    rows = [
        (c.freq, c.snr, c.dm, c.acc, c.nh, c.folded_snr, c.opt_period)
        for c in res.candidates
    ]
    with open(out_path, "wb") as f:
        pickle.dump(
            {
                "rank": jax.process_index(),
                "nproc": jax.process_count(),
                "rows": rows,
                "n_accel_trials": res.n_accel_trials,
            },
            f,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
