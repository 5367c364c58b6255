"""Test harness: force an 8-virtual-device CPU platform BEFORE jax import.

The reference has no tests (survey §4); this suite follows the survey's
recommended strategy — mesh/sharding code runs on CPU-simulated devices so
multi-chip paths are exercised without a TPU slice.
"""

import os

# Must be set before jax (or anything importing jax) loads. Force-set (not
# setdefault): whatever platform the environment names, the suite is designed
# for the 8-virtual-device CPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

from rag_llm_k8s_tpu.obs import goodput  # noqa: E402

# The goodput ledger prices chip time against roofline peaks keyed by
# device_kind and refuses a kind it does not know (obs/goodput.py). The CPU
# this suite runs on has no roofline worth the name, so the suite pins
# nominal peaks for it here, once: every RELATIVE read (category split,
# bubble fraction, per-request attribution) stays defined, and absolute MFU
# on a CPU host is meaningless by construction.
goodput.DEVICE_PEAKS["cpu"] = (275.0, 1200.0)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def mesh8(devices8):
    from rag_llm_k8s_tpu.core import MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh

    return make_mesh(MeshConfig(dp=2, sp=1, tp=4), devices=devices8)


@pytest.fixture(scope="session")
def mesh_tp8(devices8):
    from rag_llm_k8s_tpu.core import MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh

    return make_mesh(MeshConfig(dp=1, sp=1, tp=8), devices=devices8)
