"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (SURVEY.md §4: mesh tests via
xla_force_host_platform_device_count). Must run before jax is imported.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Force CPU for tests even if the ambient env selects a TPU platform:
# numeric op tests must be deterministic and mesh tests need 8 devices.
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent compilation cache, placed by the package's one rule
# (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache): the golden-recall
# gate compiles the full search program; repeat suite runs pay that once.
from peasoup_tpu.utils.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def tutorial_fil():
    path = "/root/reference/example_data/tutorial.fil"
    if not os.path.exists(path):
        pytest.skip("tutorial.fil not available")
    return path


@pytest.fixture(scope="session")
def golden_xml():
    path = "/root/reference/example_output/overview.xml"
    if not os.path.exists(path):
        pytest.skip("golden overview.xml not available")
    return open(path).read()


@pytest.fixture(scope="session")
def golden_dm_list(golden_xml):
    import re

    dms = [
        float(m)
        for m in re.findall(r"<trial id='\d+'>([-\d.e+]+)</trial>", golden_xml)
    ]
    return np.array(dms[:59])


@pytest.fixture
def rng():
    return np.random.default_rng(42)
