"""Answers the TPU compiler gives without a chip, kept as tests.

The compiler that ships with the installed jaxlib/libtpu compiles for a TPU
v5e that is described, not attached (on-chip-measurement guide, section 2.3).
Held here: the fused Pallas LN-GRU cell at the Dreamer sizes — "eligible"
must imply "compiles". Nothing here runs on a device, and nothing here is a
chip measurement. (The bound, the warning and the cache placement are in
tests/test_core/test_tpu_aot.py.)
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from sheeprl_tpu.models import pallas_gru  # noqa: E402


@pytest.fixture(scope="module")
def one_described_chip():
    try:
        topo = chip_smoke.described_v5e()
    except Exception as err:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"the TPU topology cannot be described here: {err}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one (the next compile would warn): keep the
    # cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("size", sorted(chip_smoke.GRU_SHAPES))
def test_ln_gru_eligible_implies_compiles(size, dtype, one_described_chip):
    batch, hidden, d = chip_smoke.GRU_SHAPES[size]
    reason = pallas_gru.ineligible_reason(batch, d, hidden, jnp.dtype(dtype).itemsize)
    if size == "XL_imagination":
        # B=1024 x 3H=12288 keeps 50-64 MiB of blocks per grid step; the
        # compiler's scoped-VMEM limit is 16 MiB. Declared ineligible, and
        # the compiler agrees.
        assert reason is not None and "VMEM" in reason
        with pytest.raises(Exception, match="(?i)vmem"):
            chip_smoke.compile_ln_gru(batch, hidden, d, dtype, one_described_chip)
        return
    assert reason is None
    compiled = chip_smoke.compile_ln_gru(batch, hidden, d, dtype, one_described_chip)
    assert "tpu_custom_call" in compiled.as_text()
