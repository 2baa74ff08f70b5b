#!/usr/bin/env python3
"""Device time of the sparse decoders' grouped expert products: the Pallas
kernel ``ops/grouped_matmul.gmm`` at several out tiles against
``lax.ragged_dot``, at the shapes the served cells call it with, and the
hybrid's decode-step expert layer in its batched and its grouped form.

Shapes (rows, experts, in, out; the held rows' group sizes drawn from
``--seed``):

* ``trinity_prefill``: a slab of Trinity's prompt, 12,288 rows, 32 held
  experts, 3,072 -> 3,072; ~6,144 held rows in ~25 touched groups, uneven;
* ``trinity_prefill_even``: the same slab, 192 rows in each of the 32;
* ``trinity_decode``: 32 rows x top 4 = 128 rows, ~16 held in 6 groups;
  ``trinity_decode_1row``: 32 rows, 4 held in 4 groups;
* ``hybrid_wave``, ``hybrid_wave_full``: the hybrid's prefill wave, 3,584
  and 8,192 tokens x 22, a quarter held over its 128 experts, 1,024 ->
  2,688 (up) and 2,688 -> 1,024 (down);
* ``hybrid_decode``: 64 rows x 22, 352 held over ~115 of 128, up and down;
* the whole decode-step expert layer at 64 tokens, batched over every held
  expert, grouped through the kernel and through ``ragged_dot``: the
  hybrid's (``layer_*``: 22 of 512 into a 1,024-wide latent, 128 held,
  relu^2) and the one of full-width gated experts (``gated_layer_*``: a
  softmax over the top 10 of 72 logits, 36 held, 4,096 -> 768, a gated
  shared expert of 1,536).

Each program runs ``--calls`` times under the profiler; its time is the
device self time of its operations over the calls
(``scripts/trace_scopes.py``). A chip run only: the CPU gives no time
(``--rehearsal`` under ``JAX_PLATFORMS=cpu`` runs toy shapes through the
same code and prints no time).

Usage: python3 scripts/gmm_timing.py [--out DIR] [--calls 30] [--seed 0]
       JAX_PLATFORMS=cpu python3 scripts/gmm_timing.py --rehearsal
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental.pallas.ops.tpu import megablox  # noqa: E402

from eventgpt_tpu.models import experts as ex  # noqa: E402
from eventgpt_tpu.ops import grouped_matmul as gm  # noqa: E402
from eventgpt_tpu.utils.platform import backend_platform  # noqa: E402
from scripts.trace_scopes import seconds_by_scope  # noqa: E402

# The weight-block budgets swept: at 3,072 in they give out tiles of 256,
# 512, 768, 1,024, 1,536 and 3,072.
BUDGETS = (3 << 19, 3 << 20, 9 << 19, 3 << 21, 9 << 20, 19 << 20)


def uneven(rng, rows: int, groups: int, touched: int, held: int):
    """``held`` rows over ``touched`` of ``groups`` groups, skewed."""
    sizes = np.zeros(groups, np.int64)
    chosen = rng.choice(groups, touched, replace=False)
    share = rng.dirichlet(np.full(touched, 0.7))
    sizes[chosen] = 1 + rng.multinomial(held - touched, share)
    assert sizes.sum() == held <= rows
    return sizes.astype(np.int32)


def shapes(rng, toy: bool):
    """name -> (rows, experts, [(in, out), ...], sizes)."""
    if toy:
        return {"toy_prefill": (512, 4, [(32, 256)],
                                uneven(rng, 512, 4, 3, 250)),
                "toy_decode": (128, 4, [(32, 256)], np.array([3, 0, 5, 1],
                                                              np.int32))}
    even = np.full(32, 192, np.int32)
    return {
        "trinity_prefill": (12288, 32, [(3072, 3072)],
                            uneven(rng, 12288, 32, 25, 6144)),
        "trinity_prefill_even": (12288, 32, [(3072, 3072)], even),
        "trinity_decode": (128, 32, [(3072, 3072)],
                           uneven(rng, 128, 32, 6, 16)),
        "trinity_decode_1row": (32, 32, [(3072, 3072)],
                                uneven(rng, 32, 32, 4, 4)),
        "hybrid_wave": (3584 * 22, 128, [(1024, 2688), (2688, 1024)],
                        uneven(rng, 3584 * 22, 128, 128, 3584 * 22 // 4)),
        "hybrid_wave_full": (8192 * 22, 128, [(1024, 2688), (2688, 1024)],
                             uneven(rng, 8192 * 22, 128, 128,
                                    8192 * 22 // 4)),
        "hybrid_decode": (64 * 22, 128, [(1024, 2688), (2688, 1024)],
                          uneven(rng, 64 * 22, 128, 115, 352)),
    }


def named(fn, name: str):
    fn.__name__ = name
    return jax.jit(fn)


def programs(rng, toy: bool):
    """(program name -> (jitted fn, args), checks: name -> (kernel program,
    ragged program, held rows))."""
    out, checks, weights = {}, {}, {}
    for case, (rows, e, dims, sizes) in shapes(rng, toy).items():
        s = jnp.asarray(sizes)
        print(f"[gmm] {case}: rows {rows}, held {int(sizes.sum())} in "
              f"{int((sizes > 0).sum())} groups; (128-row tile, group) pairs "
              f"{int(gm.tiles(s))}, (512-row tile, group) pairs "
              f"{int(gm.tiles(s, 512))}", flush=True)
        for d_in, d_out in dims:
            kx, kw = jax.random.split(jax.random.PRNGKey(d_in + d_out))
            x = jax.random.normal(kx, (rows, d_in), jnp.bfloat16)
            if (e, d_in, d_out) not in weights:   # one tensor a shape
                weights[e, d_in, d_out] = (jax.random.normal(
                    kw, (e, d_in, d_out), jnp.bfloat16)
                    / np.sqrt(d_in)).astype(jnp.bfloat16)
            w = weights[e, d_in, d_out]
            tag = f"{case}_{d_in}x{d_out}"
            out[f"ragged_{tag}"] = (named(
                lambda x, w, s: lax.ragged_dot(x, w, s),
                f"ragged_{tag}"), (x, w, s))
            seen = set()
            for budget in BUDGETS:
                # Both budgets are read while tracing.
                gm.WEIGHT_BLOCK_BYTES = gm.ONE_TILE_WEIGHT_BYTES = budget
                tn = gm.out_tile(rows, d_in, d_out, 2)
                if tn in seen:
                    continue
                seen.add(tn)
                name = f"gmm_{tag}_tn{tn}"
                fn = named(lambda x, w, s: gm.gmm.__wrapped__(x, w, s),
                           name)
                fn(x, w, s).block_until_ready()   # traced at this budget
                out[name] = (fn, (x, w, s))
                checks[name] = (f"ragged_{tag}", int(sizes.sum()))
            if case in ("trinity_prefill", "toy_prefill"):
                # Megablox's own kernel, its `k` tiles inner, for reference.
                for tiling in ((128, 128, 128), (128, 512, 512)):
                    name = f"megablox_{tag}_{'x'.join(map(str, tiling))}"
                    out[name] = (named(
                        lambda x, w, s, tiling=tiling: megablox.gmm(
                            x, w, s, preferred_element_type=x.dtype,
                            tiling=tiling, interpret=not on_chip()),
                        name), (x, w, s))
                    checks[name] = (f"ragged_{tag}", int(sizes.sum()))
    return out, checks


def on_chip() -> bool:
    return backend_platform() == "tpu"


def hybrid_layer(toy: bool, gated: bool = False):
    """A decode-step expert layer, 64 tokens: batched over every held
    expert, grouped through the kernel, grouped through ragged_dot. The
    hybrid's (relu^2 in a latent, sigmoid router), or, ``gated``, gated
    experts on the full width routed by a softmax over the top k logits."""
    if gated:
        d, lat, f, held, width, k = ((64, None, 48, 8, 16, 5) if toy
                                     else (4096, None, 768, 36, 72, 10))
    else:
        d, lat, f, held, width, k = ((64, 32, 256, 8, 32, 4) if toy
                                     else (4096, 1024, 2688, 128, 512, 22))
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 12))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(shape[-2])).astype(jnp.bfloat16)

    e = lat or d  # the routed experts' input width
    if gated:
        routing = ex.Routing(top_k=k, held=held, offset=0, normalise=False,
                             scale=1.0, form="softmax_topk")
        layer = dict(router=jax.random.normal(next(keys), (d, width)) / 64,
                     bias=None,
                     experts={"gate": dense(held, e, f), "up": dense(held, e, f),
                              "down": dense(held, f, e)},
                     shared={"gate": dense(d, 2 * f), "up": dense(d, 2 * f),
                             "down": dense(2 * f, d)})
    else:
        routing = ex.Routing(top_k=k, held=held, offset=0, normalise=True,
                             scale=5.0)
        layer = dict(router=jax.random.normal(next(keys), (d, width)) / 64,
                     bias=jnp.zeros((width,)),
                     experts={"up": dense(held, e, f),
                              "down": dense(held, f, e)},
                     shared={"up": dense(d, 2 * f), "down": dense(2 * f, d)},
                     latent=(dense(d, lat), dense(lat, d)))
    y = jax.random.normal(next(keys), (64, d), jnp.float32)
    counted = jnp.ones((64,), bool)
    out = {}
    prefix = "gated_layer" if gated else "layer"
    for name, dense_up_to, product in (
            (prefix + "_batched", 128, None), (prefix + "_gmm", 0, gm.gmm),
            (prefix + "_ragged", 0, lax.ragged_dot)):
        def fn(y, layer, dense_up_to=dense_up_to, product=product):
            saved = ex._product
            if product is not None:
                ex._product = lambda: product
            try:
                return ex.sparse_experts(routing, y, counted, jnp.bfloat16,
                                         dense_up_to=dense_up_to, **layer)
            finally:
                ex._product = saved
        f_ = named(fn, name)
        jax.block_until_ready(f_(y, layer))   # traced with its product
        out[name] = (f_, (y, layer))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=".bench_out/gmm_timing")
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    chip = on_chip()
    if not chip and not args.rehearsal:
        sys.stderr.write("a chip run only (--rehearsal on the CPU)\n")
        return 2
    toy = args.rehearsal
    rng = np.random.default_rng(args.seed)
    progs, checks = programs(rng, toy)
    progs.update(hybrid_layer(toy))
    progs.update(hybrid_layer(toy, gated=True))
    # On the host, the held rows only: a wave's outputs are ~1 GB each.
    for name, (ragged, held) in checks.items():
        fn, a = progs[name]
        got = np.asarray(fn(*a)[:held], np.float32)
        fn, a = progs[ragged]
        want = np.asarray(fn(*a)[:held], np.float32)
        gap = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                     1e-30))
        print(f"[gmm] {name}: widest gap to ragged_dot over the held rows, "
              f"over the largest |value|: {gap:.3g}", flush=True)
    for prefix in ("layer", "gated_layer"):
        fn, a = progs[prefix + "_batched"]
        base = np.asarray(fn(*a)[0], np.float32)
        for name in (prefix + "_gmm", prefix + "_ragged"):
            fn, a = progs[name]
            got = np.asarray(fn(*a)[0], np.float32)
            print(f"[gmm] {name}: widest gap to {prefix}_batched "
                  f"{float(np.max(np.abs(got - base))):.3g} (largest |value| "
                  f"{float(np.max(np.abs(base))):.3g})", flush=True)
    if not chip:
        print("[gmm] rehearsal: no time on the CPU")
        return 0
    os.makedirs(args.out, exist_ok=True)
    # One capture a program: two programs of one shape share an executable's
    # fingerprint, and a capture names both after one of them.
    table = {}
    for name, (fn, a) in progs.items():
        where = os.path.join(args.out, name)
        jax.profiler.start_trace(where)
        for _ in range(args.calls):
            r = fn(*a)
        jax.block_until_ready(r)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        table[name] = collections.defaultdict(float)
        for (_, scope, _), sec in seconds_by_scope(path, by_op=True).items():
            table[name]["ms"] += 1e3 * sec / args.calls
            if scope == "moe_experts":
                table[name]["moe_experts_ms"] += 1e3 * sec / args.calls
        line = f"[gmm] {name:44s} {table[name]['ms']:9.4f} ms a call"
        if "moe_experts_ms" in table[name]:
            line += f", moe_experts {table[name]['moe_experts_ms']:.4f} ms"
        print(line, flush=True)
        shutil.rmtree(where)
    with open(os.path.join(args.out, "times.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
