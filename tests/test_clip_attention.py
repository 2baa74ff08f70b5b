"""The event tower's attention kernel (``ops/clip_attention.py``) against its
XLA form: on the CPU in interpreter mode at small shapes, an unaligned
length and the tower's 577 tokens among them; the tower's choice of form by
platform; and compiled (never run) for a described TPU v5e at the served
shape. float32 operands: the tolerance is that of two orders of float32
summation; bfloat16 ones, one bfloat16 rounding."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import EventChatConfig, VisionConfig
from eventgpt_tpu.models import clip, eventchat
from eventgpt_tpu.ops import clip_attention as ca

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")


def operands(frames, tokens, heads, head_dim, dtype=jnp.float32, seed=0):
    """q (scaled, as the tower hands it over), k, v: (N, S, H * hd)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(key, (frames, tokens, heads * head_dim))
               for key in keys)
    return ((q / np.sqrt(head_dim)).astype(dtype), k.astype(dtype),
            v.astype(dtype))


@pytest.mark.parametrize(
    "frames, tokens, heads, head_dim, dtype, tol, lanes", [
        (2, 37, 4, 64, jnp.float32, 1e-5, 128),   # unaligned, two cells
        (1, 577, 2, 64, jnp.float32, 1e-5, 128),  # the tower's frame
        (2, 37, 4, 64, jnp.bfloat16, 1e-2, 128),
        (2, 37, 4, 64, jnp.float32, 1e-5, 256),   # a cell of two chunks
        (1, 9, 16, 64, jnp.float32, 1e-5, 512),   # two cells of four chunks
        (1, 40, 3, 16, jnp.float32, 1e-5, 128),   # a toy width: one chunk
        (1, 9, 12, 64, jnp.float32, 1e-5, 512),   # 768: three of 256
        (1, 9, 10, 64, jnp.float32, 1e-5, 512),   # 640: five of 128
    ], ids=["37-tokens", "577-tokens", "bfloat16", "256-lane-cells",
            "the-towers-heads", "toy-width", "12-heads", "10-heads"])
def test_kernel_against_the_xla_form(frames, tokens, heads, head_dim, dtype,
                                     tol, lanes, monkeypatch):
    monkeypatch.setattr(ca, "BLOCK_LANES", lanes)
    q, k, v = operands(frames, tokens, heads, head_dim, dtype)
    got = ca._forward.__wrapped__(q, k, v, heads)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ca.xla_attention(q, k, v, heads), np.float32),
        rtol=tol, atol=tol)


def test_block_lanes():
    """Eight heads of 64 a cell at the tower's width, two at a time; cells
    that tile a width 512 does not divide; a width that 128 lanes of whole
    heads cannot tile is one cell."""
    assert ca.block_lanes(16, 64) == (512, 128)
    assert ca.block_lanes(12, 64) == (256, 128)
    assert ca.block_lanes(10, 64) == (128, 128)
    assert ca.block_lanes(18, 64) == (128, 128)
    assert ca.block_lanes(2, 64) == (128, 128)
    assert ca.block_lanes(3, 16) == (48, 48)
    assert ca.block_lanes(4, 96) == (384, 384)


def test_the_gradient_is_the_xla_forms():
    q, k, v = operands(1, 9, 2, 64, seed=3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, 2)))

    got = jax.grad(loss(ca.clip_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ca.xla_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.fixture
def own_traces():
    """A test that reads the platform as ``tpu`` traces afresh and leaves no
    trace behind: jit keeps a trace by its arguments' types, not by the
    platform, so a CPU trace of the tower by another test in the process
    would stand in for this one, and this one for a later CPU test's."""
    jax.clear_caches()
    yield
    jax.clear_caches()


TINY = VisionConfig(hidden_size=32, intermediate_size=64, num_layers=2,
                    num_heads=4, image_size=28, patch_size=14)


def test_the_tower_takes_the_kernel_on_the_chip(monkeypatch, own_traces):
    """``clip._attend``: the XLA form on the CPU and where the tower is
    sharded over a mesh (pinned or not), the kernel where the platform
    reads ``tpu``; the tower's last hidden state is the same through
    either."""
    params = clip.init_clip_params(TINY, jax.random.PRNGKey(1))
    pixels = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 28, 28))
    assert clip._attend(False) is ca.xla_attention
    want = clip.clip_encode(params, TINY, pixels)
    monkeypatch.setattr(clip, "backend_platform", lambda: "tpu")
    assert clip._attend(False) is ca.clip_attention
    assert clip._attend(True) is ca.xla_attention

    def jaxpr(**kw):
        return str(jax.make_jaxpr(functools.partial(
            clip.clip_encode, cfg=TINY, **kw))(params, pixel_values=pixels))

    assert "clip_attention" in jaxpr()
    assert "clip_attention" not in jaxpr(sharded=True)
    assert "clip_attention" not in jaxpr(pin=lambda x: x)
    np.testing.assert_allclose(clip.clip_encode(params, TINY, pixels), want,
                               rtol=2e-5, atol=2e-5)


# -- compiled for the chip, at the served shape (no chip: nothing runs) ------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def test_the_towers_wave_compiles_for_a_v5e(one_chip):
    """Mosaic takes the 577-row blocks under the kernel's name, and the
    program holds no temporary: no score reaches HBM. A wave of 4 requests
    is 20 frames."""
    spec = jax.ShapeDtypeStruct((20, 577, 1024), jnp.bfloat16,
                                sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: ca._forward(q, k, v, 16, interpret=False)
    ).lower(spec, spec, spec).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%clip_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("caller", ["one-chip", "train-step", "serving"])
def test_the_tower_compiles_for_a_v5e(topo, caller, monkeypatch,
                                      own_traces):
    """``encode_events_batch`` at toy widths, compiled for described chips
    as the chip's process would trace it: on one chip the tower holds the
    kernel; on a mesh of four it keeps the XLA form, which the partitioner
    can split (it refuses to split a Mosaic call), both in the train step
    (``mesh``: the carry pinned) and in a serving mesh (``sharded``: no
    pin, the program the XLA form always gave there)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    monkeypatch.setattr(clip, "backend_platform", lambda: "tpu")
    monkeypatch.setattr(ca, "pallas_interpret", lambda: False)
    cfg = EventChatConfig.tiny()
    mesh = (Mesh(np.array(topo.devices).reshape(2, 2), ("data", "fsdp"))
            if caller != "one-chip" else None)
    placed = (NamedSharding(mesh, P()) if mesh else
              jax.sharding.SingleDeviceSharding(topo.devices[0]))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed),
        jax.eval_shape(lambda: eventchat.init_eventchat_params(
            cfg, jax.random.PRNGKey(0))))
    pixels = jax.ShapeDtypeStruct(
        (4, cfg.num_event_frames, 3, 28, 28), jnp.float32,
        sharding=NamedSharding(mesh, P(("data", "fsdp"))) if mesh else placed)
    kw = {"train-step": {"mesh": mesh}, "serving": {"sharded": True}}
    lowered = eventchat.encode_events_batch.lower(
        params, cfg, pixels, **kw.get(caller, {}))
    text = lowered.as_text()
    pinned = "sharding_constraint" in text or "@Sharding" in text
    assert pinned == (caller == "train-step")
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        caller == "one-chip")
