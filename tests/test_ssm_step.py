"""The state's one-pass decode step (``ops/ssm_step.py``) against its plain
twin, ``ssm_step_reference``: on the CPU in interpreter mode at small shapes
that tile, and compiled (never run) for a described TPU v5e at the served
shape. float32 throughout: the tolerance is that of two orders of float32
summation and of a fused multiply-add."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.ops import ssm_step as mod
from eventgpt_tpu.ops.ssm_step import ssm_step, ssm_step_reference

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

# planes, rows, heads, head_dim, state, groups
TILED = (2, 3, 16, 8, 128, 2)
SERVED = (5, 64, 128, 64, 128, 8)   # nemotron3-super-120b-event, 64 rows
TOY = (5, 3, 8, 16, 16, 2)          # nemotron3-super-tiny: cannot be tiled


def operands(shape, seed=0):
    planes, rows, heads, p, n, g = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (planes, rows, heads, p, n), jnp.float32),
            jax.random.uniform(ks[1], (rows, heads), jnp.float32, 0.5, 1.0),
            jax.random.normal(ks[2], (rows, heads, p), jnp.float32),
            jax.random.normal(ks[3], (rows, g, n), jnp.float32),
            jax.random.normal(ks[4], (rows, g, n), jnp.float32))


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def kernel_is_in(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def step_a_plane(shape, i):
    """Plane ``i`` as the twin steps it, every other plane byte for byte."""
    h_buf, decay, xdt, b, c = operands(shape)
    got_h, got_y = ssm_step(h_buf, i, decay, xdt, b, c)
    want_h, want_y = ssm_step_reference(h_buf, i, decay, xdt, b, c)
    assert kernel_is_in(lambda *a: ssm_step(a[0], i, *a[1:]),
                        h_buf, decay, xdt, b, c)
    close(got_h[i], want_h[i])
    close(got_y, want_y)
    for other in range(shape[0]):
        if other != i:
            assert np.array_equal(np.asarray(got_h[other]),
                                  np.asarray(h_buf[other]))


def first_plane(_):
    step_a_plane(TILED, 0)


def last_plane(_):
    step_a_plane((3,) + TILED[1:], 2)


def several_blocks_a_row(monkeypatch):
    """A row's groups in more than one grid cell: two groups of the four a
    cell, as the served shape's four of eight."""
    shape = (2, 2, 32, 8, 128, 4)
    monkeypatch.setattr(mod, "BLOCK_BYTES", 2 * 8 * 8 * 128 * 4)
    assert mod._groups_per_block(4, 8, 8, 128) == 2
    step_a_plane(shape, 1)


def rows_that_are_not_live_keep_h_bit_for_bit(_):
    h_buf, decay, xdt, b, c = operands(TILED, seed=1)
    idle = jnp.array([True, False, True])
    decay = jnp.where(idle[:, None], 1.0, decay)       # dt = 0
    xdt = jnp.where(idle[:, None, None], 0.0, xdt)
    got_h, got_y = ssm_step(h_buf, 1, decay, xdt, b, c)
    want_h, want_y = ssm_step_reference(h_buf, 1, decay, xdt, b, c)
    for row in (0, 2):
        assert np.array_equal(np.asarray(got_h[1, row]),
                              np.asarray(h_buf[1, row]))
    close(got_h[1, 1], want_h[1, 1])
    close(got_y, want_y)                                # idle rows: h . C


def shapes_that_cannot_be_tiled_take_the_twin(_):
    args = operands(TOY)
    assert not mod.tileable(TOY[3], TOY[4]) and mod.tileable(64, 128)
    assert not kernel_is_in(lambda *a: ssm_step(a[0], 4, *a[1:]), *args)
    got_h, got_y = ssm_step(args[0], 4, *args[1:])
    want_h, want_y = jax.jit(ssm_step_reference, static_argnums=1)(
        args[0], 4, *args[1:])
    assert np.array_equal(np.asarray(got_h), np.asarray(want_h))
    assert np.array_equal(np.asarray(got_y), np.asarray(want_y))


def the_kernel_hands_its_buffer_back(_):
    """The stacked buffer is the kernel's first operand and its first
    result, aliased; a caller that donates it gets the same buffer back."""
    args = operands(TILED)
    jaxpr = str(jax.make_jaxpr(lambda *a: ssm_step(a[0], 1, *a[1:]))(*args))
    assert "input_output_aliases=((0, 0),)" in jaxpr
    step = jax.jit(lambda *a: ssm_step(a[0], 1, *a[1:]), donate_argnums=0)
    assert "tf.aliasing_output = 0" in step.lower(*args).as_text()
    h_buf = args[0] + 0.0
    got_h, _ = step(h_buf, *args[1:])
    assert h_buf.is_deleted()
    close(got_h[1], ssm_step_reference(args[0], 1, *args[1:])[0][1])


@pytest.mark.parametrize("case", [
    first_plane, last_plane, several_blocks_a_row,
    rows_that_are_not_live_keep_h_bit_for_bit,
    shapes_that_cannot_be_tiled_take_the_twin,
    the_kernel_hands_its_buffer_back,
], ids=lambda case: case.__name__)
def test_kernel_against_its_twin(case, monkeypatch):
    case(monkeypatch)


# -- compiled for the chip, at the served shape (no chip: nothing runs) -------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_served_shape_compiles_for_a_v5e_in_place(one_chip):
    """Mosaic takes the kernel at 64 rows x 128 heads x 64 x 128; the
    program holds no second buffer of ``h_buf``'s size, nor of a plane's."""
    planes, rows, heads, p, n, g = SERVED

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda h_buf, *rest: ssm_step(h_buf, 3, *rest, interpret=False),
        donate_argnums=0,
    ).lower(spec(planes, rows, heads, p, n), spec(rows, heads),
            spec(rows, heads, p), spec(rows, g, n), spec(rows, g, n)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    memory = compiled.memory_analysis()
    buffer_bytes = planes * rows * heads * p * n * 4
    assert memory.alias_size_in_bytes == buffer_bytes
    assert memory.temp_size_in_bytes < buffer_bytes // planes // 8
    assert mod._groups_per_block(g, heads // g, p, n) == 4


def test_one_group_of_every_head_compiles_for_a_v5e_in_place(one_chip):
    """granite-4.0-h-small-event's state, nine planes of 64 rows x 128
    heads x 64 x 128 in one group of B / C: a group is 4 MB, over
    ``BLOCK_BYTES``, so a grid cell holds a row's every head (the
    fallback of ``_groups_per_block``), and Mosaic takes it within the
    kernel's scoped VMEM."""
    planes, rows, heads, p, n, g = 9, 64, 128, 64, 128, 1
    assert mod._groups_per_block(g, heads // g, p, n) == g
    assert heads * p * n * 4 > mod.BLOCK_BYTES

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda h_buf, *rest: ssm_step(h_buf, 8, *rest, interpret=False),
        donate_argnums=0,
    ).lower(spec(planes, rows, heads, p, n), spec(rows, heads),
            spec(rows, heads, p), spec(rows, g, n), spec(rows, g, n)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == planes * rows * heads * p * n * 4
    assert memory.temp_size_in_bytes < rows * heads * p * n * 4 // 8
