"""The grouped expert product's capacity (``models/experts.py``): slabs of
the sorted held assignments against the one grouped product over every
assignment, on the same inputs, for routers that stay under the capacity,
pass it, and hold nothing. float32 on the CPU, jitted as the server runs the
layer; the two paths sum a token's terms in the same order but meet the
shared expert's sum in other fusions, so the tolerance is float32 rounding
(1e-5 on outputs of magnitude 1-10), never bfloat16's."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.models import experts as ex
from eventgpt_tpu.serve import _expert_counts

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

D, F, LATENT, HELD, WIDTH, K, OFFSET = 32, 48, 16, 4, 32, 4, 8
# 500 tokens: 2,000 assignments, a capacity of 512 rows (twice the even share
# of 250, in tiles of 128), which does not divide them: the last slab is cut.
T = 500
ROUTING = ex.Routing(top_k=K, held=HELD, offset=OFFSET, normalise=True,
                     scale=2.0)
FORMS = [(kind, latent) for kind in ("swiglu", "relu2")
         for latent in (False, True)]
IDS = [f"{kind}{'-latent' if latent else ''}" for kind, latent in FORMS]


def layer_of(kind: str, latent: bool):
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 12))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(shape[-2]))

    inner = LATENT if latent else D
    names = ("gate", "up") if kind == "swiglu" else ("up",)
    return {
        "router": dense(D, WIDTH),
        "experts": {**{n: dense(HELD, inner, F) for n in names},
                    "down": dense(HELD, F, inner)},
        "shared": {**{n: dense(D, F) for n in names}, "down": dense(F, D)},
        "latent": (dense(D, LATENT), dense(LATENT, D)) if latent else None,
    }


FACTOR = ex.CAPACITY_FACTOR


def run(layer, bias, counted, monkeypatch, t=T, whole=False):
    """The layer jitted anew (the capacity is read while tracing). ``whole``:
    a huge ``CAPACITY_FACTOR`` leaves nothing to cut, which is the one
    grouped product over every assignment."""
    monkeypatch.setattr(ex, "CAPACITY_FACTOR", 10 ** 6 if whole else FACTOR)
    y = jax.random.normal(jax.random.PRNGKey(3), (t, D), jnp.float32)
    fn = jax.jit(lambda y, bias: ex.sparse_experts(
        ROUTING, y, counted, jnp.float32, router=layer["router"], bias=bias,
        experts=layer["experts"], shared=layer["shared"],
        latent=layer["latent"]))
    out, stats = fn(y, bias)
    return np.asarray(out), np.asarray(stats), fn, y


def both_paths(layer, bias, counted, monkeypatch):
    got, stats, _, _ = run(layer, bias, counted, monkeypatch)
    want, want_stats, _, _ = run(layer, bias, counted, monkeypatch,
                                 whole=True)
    assert want_stats[4] == 0  # the whole path has no capacity to pass
    np.testing.assert_array_equal(stats[:4], want_stats[:4])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    return got, stats


def biased(value: float):
    """The router's choice pushed onto (or off) the held experts."""
    return jnp.zeros((WIDTH,), jnp.float32).at[
        OFFSET:OFFSET + HELD].set(value)


def test_the_capacity_at_the_served_shapes():
    """Trinity's prompt is cut to a quarter; its decode step, the hybrid's
    wave (a quarter of the experts held: twice the even share is half) and a
    decoder that holds every expert have nothing to cut."""
    assert ex.capacity(12288 * 4, 32, 256) == 12288
    assert ex.capacity(T * K, HELD, WIDTH) == 512 and 2 * 512 < T * K
    for a, held, width in ((32 * 4, 32, 256), (3584 * 22, 128, 512),
                           (8192 * 22, 128, 512), (29 * 4, 32, 32)):
        assert 2 * ex.capacity(a, held, width) >= a
    assert ex.capacity(4, 1, 256) == ex.ROW_TILE  # never under one tile


@pytest.mark.parametrize("kind, latent", FORMS, ids=IDS)
def test_an_even_router_fills_one_slab(kind, latent, monkeypatch):
    counted = jnp.ones((T,), bool)
    _, stats = both_paths(layer_of(kind, latent), biased(0.0), counted,
                          monkeypatch)
    assert 0 < stats[2] <= 512 and stats[4] == 0 and stats[3] == T


@pytest.mark.parametrize("bias", [10.0, 0.2], ids=["all-held", "some-over"])
@pytest.mark.parametrize("kind, latent", FORMS, ids=IDS)
def test_a_router_that_passes_the_capacity_runs_every_slab(
        kind, latent, bias, monkeypatch):
    """Every assignment on a held expert (2,000 rows: four slabs, the last
    cut short), and a router that passes the capacity by a part of a slab:
    no assignment is dropped, and the counter says a second slab ran."""
    counted = jnp.ones((T,), bool)
    _, stats = both_paths(layer_of(kind, latent), biased(bias), counted,
                          monkeypatch)
    assert stats[2] > 512 and stats[4] == 1
    assert (stats[2] == T * K) == (bias == 10.0)


@pytest.mark.parametrize("kind, latent", FORMS, ids=IDS)
def test_no_held_assignment_at_all(kind, latent, monkeypatch):
    """Nothing falls on a held expert: the layer is its shared expert."""
    counted = jnp.ones((T,), bool)
    layer = layer_of(kind, latent)
    got, stats = both_paths(layer, biased(-10.0), counted, monkeypatch)
    assert stats.tolist() == [0, 0, 0, T, 0]
    y = jax.random.normal(jax.random.PRNGKey(3), (T, D), jnp.float32)
    np.testing.assert_allclose(got, np.asarray(ex._one(y, layer["shared"])),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind, latent", FORMS, ids=IDS)
def test_padded_tokens_are_computed_and_not_counted(kind, latent,
                                                    monkeypatch):
    """A hundred real tokens of 500, every assignment held: the padded rows'
    outputs are the whole path's too, the four counters count the real
    tokens alone (400 held assignments, under the capacity), and the fifth
    counts what was computed: 2,000 rows, four slabs."""
    counted = jnp.arange(T) < 100
    layer = layer_of(kind, latent)
    got, stats = both_paths(layer, biased(10.0), counted, monkeypatch)
    assert stats.tolist() == [HELD, stats[1], 100 * K, 100, 1]
    alone, _ = both_paths(layer, biased(10.0), jnp.ones((T,), bool),
                          monkeypatch)
    np.testing.assert_array_equal(got, alone)


@pytest.mark.parametrize("kind, latent", FORMS, ids=IDS)
def test_a_call_with_nothing_to_cut_is_the_one_grouped_product(
        kind, latent, monkeypatch):
    """29 tokens (116 assignments, one tile of capacity: more than half):
    the program holds no loop and no branch, as before there was a capacity;
    500 tokens' does hold the loop."""
    layer = layer_of(kind, latent)
    for t, looped in ((29, False), (T, True)):
        _, stats, fn, y = run(layer, biased(0.0), jnp.ones((t,), bool),
                              monkeypatch, t=t)
        text = str(jax.make_jaxpr(fn)(y, biased(0.0)))
        assert ("while" in text) == looped and "cond[" not in text
        assert stats[4] == 0


def test_the_span_args_carry_the_fifth_counter():
    counted = np.zeros((3, 2, len(ex.STATS)), np.int32)
    counted[0] = [[3, 5, 9, 4, 1], [2, 4, 7, 4, 0]]
    counted[2] = [[1, 1, 1, 1, 0], [1, 1, 1, 1, 0]]  # step 1 ran for no row
    args = _expert_counts(counted)
    assert args["experts_over_capacity"] == [[1, 0], [0, 0]]
    assert args["held_assignments"] == [[9, 7], [1, 1]]
    assert args["routed_tokens"] == [4, 1]
