"""Compile the golden-section kernel for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(VMEM overruns, tilings Mosaic cannot lower, unlowerable primitives) —
faults that interpret mode on the CPU never shows. The topology is
described inside a fixture, only once a test of this file runs, because the
TPU library may be loaded by one process at a time.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import resource_allocation as ra
from repro.kernels.golden_section import golden_section_solve


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (profile, G, R): the kernel's default-profile widths, the live loop's
# coarse profile, and the widest reach bucket's refresh batch at
# N=20k/K=200 (R_b + 1 candidate groups of width R_b = 1455), which
# overran scoped VMEM while a block held a fixed 256 groups
KERNEL_CASES = [("default", 512, 64), ("default", 4096, 64),
                ("coarse", 4096, 64), ("coarse", 1456, 1455)]


@pytest.mark.parametrize("profile,g,r", KERNEL_CASES,
                         ids=[f"{p}-G{g}-R{r}" for p, g, r in KERNEL_CASES])
def test_golden_section_kernel_compiles(one_chip, profile, g, r):
    f32 = jax.ShapeDtypeStruct((g, r), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((g,), jnp.float32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((g, r), jnp.bool_, sharding=one_chip)
    solve = jax.jit(lambda a, b, d, e, w_, fmin, fmax, m: golden_section_solve(
        a, b, d, e, w_, fmin, fmax, m, interpret=False,
        **ra.SCREEN_PROFILES[profile]))
    compiled = solve.lower(f32, f32, f32, f32, w, f32, f32, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()

