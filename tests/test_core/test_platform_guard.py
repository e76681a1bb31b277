"""force_cpu_platform (core/runtime.py): the CPU selection every
``fabric.accelerator=cpu`` launch makes must leave a platform that is already
built alone — the suite constructs many Runtimes mid-session, and rebuilding
the backends would invalidate every live array in the process.
"""

import jax

from sheeprl_tpu.core.runtime import force_cpu_platform


def test_force_cpu_platform_leaves_a_built_platform_alone():
    # The suite's conftest sized the CPU platform at 8 devices and it is
    # built by now: asking for fewer (num_devices is a minimum) or for more
    # (the client is sized once) must not rebuild it.
    before = jax.devices()
    arr = jax.numpy.ones((4,)) + 1  # a live array a rebuild would kill
    force_cpu_platform()
    force_cpu_platform(num_devices=2)
    force_cpu_platform(num_devices=16)
    assert jax.devices() == before
    assert float(arr.sum()) == 8.0
    assert jax.config.jax_platforms == "cpu"
