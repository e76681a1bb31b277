"""What was learned by asking the TPU compiler without a chip, kept as tests.

Held here: the VMEM bound of the fused Pallas LN-GRU cell against the totals
the compiler itself reported, the warning when an ineligible shape is
skipped, and where the persistent compile cache is placed. The compiles
themselves ("eligible" must imply "compiles", ~15 s) are in
tests/test_utils/test_tpu_aot_compiles.py, at the end of the collection
order (tier-1 is cut at its time limit today, ROADMAP D10). Nothing here
runs on a device, and nothing here is a chip measurement.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from sheeprl_tpu.core.runtime import Runtime  # noqa: E402
from sheeprl_tpu.models import pallas_gru  # noqa: E402


def test_vmem_bound_is_the_compilers_count():
    # The compiler's own totals for these shapes (f32): 6.31, 13.50, 12.60,
    # 14.16 and 64.39 MiB. The bound must never sit below them, and stays
    # within 10 % above.
    counted = {"S_train": 6.31, "S_imagination": 13.50, "M_train": 12.60, "XL_train": 14.16, "XL_imagination": 64.39}
    for size, mib in counted.items():
        batch, hidden, d = chip_smoke.GRU_SHAPES[size]
        bound = pallas_gru._vmem_bytes(batch, d, hidden, 4) / 2**20
        assert mib <= bound <= 1.10 * mib, (size, bound, mib)


def test_fused_cell_says_when_it_skips_an_ineligible_shape(monkeypatch):
    # Asked for on a TPU, refused for the shape: a warning naming the shape
    # and the bound, then the plain-JAX cell.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    inp = jnp.zeros((8, 200), jnp.float32)
    w = jnp.zeros((200, 3 * 100), jnp.float32)
    h = jnp.zeros((8, 100), jnp.float32)
    with pytest.warns(UserWarning, match=r"inp\[8, 200\].*hidden size 100 is not a multiple"):
        assert pallas_gru._eligible(inp, w, h) is False


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_the_environment_is_left_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jax.config.update("jax_compilation_cache_dir", "/what/jax/read/at/import")
    Runtime(accelerator="cpu").launch()
    assert jax.config.jax_compilation_cache_dir == "/what/jax/read/at/import"


def test_cache_dir_defaults_to_the_checkout(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)  # resolved from the package, never the cwd
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    Runtime(accelerator="cpu").launch()
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
