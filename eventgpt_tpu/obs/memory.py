"""HBM memory ledger — per-component device-byte accounting (ISSUE 9).

The serving stack has deep latency/goodput observability but was blind
on the axis that actually caps it: HBM. The batch ceiling (on the r05
chip run 40 rows ran out at run time, 48 at compile) and the prefix
cache's byte budget both manage memory with no visibility into what the
rest of the process holds. This module is the instrument that says
where every byte lives, BEFORE the paged-KV block-pool refactor
(ROADMAP item 2) redistributes them:

  * **Ledger** (``LEDGER``, process-global, thread-safe): named
    components — weight tree, resident KV cache, logits/ids buffers,
    prefix-cache entries, mixed-segment lane buffers, Medusa/draft
    buffers, pipelined carry state — updated by explicit
    ``register``/``resize``/``release`` hooks at every allocation site
    (``ContinuousBatcher``, ``PrefixCache``, the lane allocator, model
    load). Tracks current and PEAK totals; exports ``egpt_mem_*``
    gauges and ``mem_alloc``/``mem_release`` trace instants.
  * **Static capacity model** (``estimate``): closed-form bytes per
    row / lane / entry from config — dtype, int8-KV scale planes,
    SEQ_BUCKET grain, batch — with the sharding divisors of
    ``parallel/serving.py`` applied when a mesh shape is given (batch
    over the largest dividing prefix of ``(data, fsdp)``, KV heads
    over ``model`` when divisible, weight matmuls over
    ``fsdp × model``). This is the model that predicts the ceiling
    item 2 must break, and the 13B-over-a-pod fit check
    (``tests/test_13b_readiness.py``).
  * **Compiled-footprint probe** (``compiled_stats``): pulls
    ``lowered.compile().memory_analysis()`` (temp / argument / output
    sizes) from the jit executables the scheduler already runs — the
    XLA-side bytes the ledger cannot see (fusion temps, donation
    aliases). Backend support varies; unsupported backends report
    ``{"unavailable": ...}`` instead of raising.
  * **Reconciliation** (``reconcile``): sums ``jax.live_arrays()`` and
    reports the accounted/unaccounted split — the honesty check that
    keeps the ledger from silently drifting from reality
    (``tests/test_memory_ledger.py`` holds it at ≥ 90% on the CPU
    tiny server).

Like the rest of ``obs/``, the ledger core is jax-free (host ints under
one lock; ``reconcile``/``abstract_params_bytes`` import jax lazily)
and chain-neutral: it reads sizes and counts allocations, never a jax
value — chains are byte-identical with the ledger armed or idle. Lock
order: callers may hold their own lock (``PrefixCache._lock``) when
calling in; the ledger lock is a leaf below them and above the metric
locks (``caller -> MemoryLedger._lock -> _Metric._lock``, never
reversed).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

from eventgpt_tpu.obs import metrics as obs_metrics
from eventgpt_tpu.obs import trace as obs_trace

# The component catalogue (OBSERVABILITY.md "Memory ledger"). A CLOSED
# set on purpose: component names become the egpt_mem_component_bytes
# label values (METRIC_LABELS enum, lint rule 5 — bounded cardinality).
COMPONENTS = ("weights", "kv_cache", "kv_pool", "kv_block_table", "logits",
              "ids_buf", "prefix_cache", "lanes", "draft", "carry", "spill",
              "other")


class MemoryLedger:
    """Process-global device-byte ledger: ``(component, key)`` -> bytes.

    ``key`` namespaces an entry to its owner (``"b1a2f/kv_cache"``) so a
    fleet of in-process replicas can each report THEIR resident bytes
    (``snapshot(owner=...)``) while the process totals stay the sum.
    Registering an existing key is a resize (idempotent re-registration
    of a shared weight tree costs nothing); ``release`` drops the entry.

    Thread-safety: the scheduler thread registers/releases while HTTP
    handler threads read ``summary()`` — every mutation and compound
    read takes ``_lock``. Peak tracking (``peak_bytes``) is phase-scoped
    via ``reset_peak()`` (like ``reset_serving_stats``)."""

    # Lock-discipline contract (egpt-check rule ``lock``): byte counters
    # and the entry map only move under the ledger lock. The last
    # reconcile results are snapshot/flag reads (``/w``) — swapped
    # whole under the lock, read lock-free by summary consumers.
    _GUARDED_BY = {
        "_entries": "_lock",
        "_component_totals": "_lock",
        "total_bytes": "_lock",
        "peak_bytes": "_lock",
        "_live_bytes": "_lock/w",
        "_unaccounted_bytes": "_lock/w",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], int] = {}
        self._component_totals: Dict[str, int] = {}
        self.total_bytes = 0
        self.peak_bytes = 0
        # Last reconcile() results (None until the first run): summary()
        # reads them lock-free — GET /memory refreshes, /stats must not
        # walk jax.live_arrays() once per scheduler step.
        self._live_bytes: Optional[int] = None
        self._unaccounted_bytes: Optional[int] = None

    def register(self, component: str, key: str, nbytes: int) -> None:
        """Attribute ``nbytes`` device bytes to ``(component, key)``.
        Re-registering a key RESIZES it (the delta moves the totals), so
        growth sites (lane-bucket growth, prefix-cache totals) call this
        unconditionally."""
        if component not in COMPONENTS:
            raise ValueError(
                f"unknown memory component {component!r}: one of "
                f"{COMPONENTS} (the catalogue is a closed metric-label "
                f"enum — extend COMPONENTS + METRIC_LABELS together)")
        nbytes = int(nbytes)
        with self._lock:
            old = self._entries.get((component, key), 0)
            delta = nbytes - old
            if delta == 0 and (component, key) in self._entries:
                return
            self._entries[(component, key)] = nbytes
            self._component_totals[component] = (
                self._component_totals.get(component, 0) + delta)
            self.total_bytes += delta
            if self.total_bytes > self.peak_bytes:
                self.peak_bytes = self.total_bytes
            self._export_gauges_locked(component)
        # Trace outside the lock (instants take the tracer's own lock);
        # armed tracing shows every allocation move on the timeline.
        obs_trace.instant("mem_alloc" if delta > 0 else "mem_release",
                          cat="mem", component=component,
                          delta_bytes=delta, total_bytes=nbytes)

    # resize IS register (the delta form); the alias documents intent at
    # call sites that shrink/grow an existing allocation.
    resize = register

    def release(self, component: str, key: str) -> None:
        """Drop an entry (the allocation was freed). Unknown keys are a
        no-op — release paths run in sweeps that may repeat."""
        with self._lock:
            old = self._entries.pop((component, key), None)
            if old is None:
                return
            self._component_totals[component] = (
                self._component_totals.get(component, 0) - old)
            self.total_bytes -= old
            self._export_gauges_locked(component)
        obs_trace.instant("mem_release", cat="mem", component=component,
                          delta_bytes=-old, total_bytes=0)

    def _export_gauges_locked(self, component: str) -> None:
        obs_metrics.MEM_TOTAL.set(self.total_bytes)
        obs_metrics.MEM_PEAK.set(self.peak_bytes)
        obs_metrics.MEM_COMPONENT.set(
            self._component_totals.get(component, 0), component=component)

    def reset_peak(self) -> None:
        """Phase-scope the peak to the traffic that follows."""
        with self._lock:
            self.peak_bytes = self.total_bytes
            obs_metrics.MEM_PEAK.set(self.peak_bytes)

    def component_bytes(self, component: str) -> int:
        with self._lock:
            return self._component_totals.get(component, 0)

    def total(self) -> int:
        with self._lock:
            return self.total_bytes

    def snapshot(self, owner: Optional[str] = None) -> Dict[str, int]:
        """Per-component byte totals; ``owner`` filters to keys under
        ``"{owner}/"`` (one replica's resident share of the process)."""
        with self._lock:
            if owner is None:
                return {c: n for c, n in
                        sorted(self._component_totals.items()) if n}
            pre = owner + "/"
            out: Dict[str, int] = {}
            for (comp, key), n in sorted(self._entries.items()):
                if key.startswith(pre):
                    out[comp] = out.get(comp, 0) + n
            return out

    def summary(self) -> Dict[str, Any]:
        """The /stats merge body: ledger totals plus the
        LAST reconcile's accounted/unaccounted split (None until one
        ran) — all host ints, no jax walk."""
        with self._lock:
            out: Dict[str, Any] = {
                "total_bytes": self.total_bytes,
                "peak_bytes": self.peak_bytes,
                "components": {c: n for c, n in
                               sorted(self._component_totals.items()) if n},
                "entries": len(self._entries),
            }
        out["live_bytes"] = self._live_bytes
        out["unaccounted_bytes"] = self._unaccounted_bytes
        return out

    def reconcile(self) -> Dict[str, Any]:
        """Honesty check: sum ``jax.live_arrays()`` and report the
        accounted/unaccounted split. The ledger attributes what the
        runtime REGISTERS; everything else (transient admission caches
        in flight, jit constants, leaked test fixtures) shows up here
        as unaccounted instead of silently vanishing. Costly relative
        to a counter read (walks every live buffer) — called from
        GET /memory, never per scheduler step."""
        import jax

        live = 0
        for arr in jax.live_arrays():
            try:
                live += arr.nbytes
            except Exception:  # a deleted/donated array mid-walk
                continue
        with self._lock:
            total = self.total_bytes
            unaccounted = live - total
            self._live_bytes = live
            self._unaccounted_bytes = unaccounted
        obs_metrics.MEM_LIVE.set(live)
        obs_metrics.MEM_UNACCOUNTED.set(unaccounted)
        return {
            "live_bytes": live,
            "accounted_bytes": total,
            "unaccounted_bytes": unaccounted,
            "accounted_ratio": (total / live) if live else 1.0,
        }


LEDGER = MemoryLedger()


def params_bytes(tree: Any) -> int:
    """Sum of leaf ``nbytes`` over a (possibly nested) param tree —
    works on concrete arrays and numpy alike (metadata only, no sync).
    The weight-tree registration helper."""
    import jax

    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
                   if hasattr(x, "nbytes")))


def abstract_params_bytes(cfg, quant: str = "bf16", dtype_bytes: int = 2
                          ) -> int:
    """Weight-tree bytes WITHOUT materializing weights: ``eval_shape``
    the init + (optional) int8 quantization transform and sum the
    abstract leaf sizes — the 13B static-capacity check's weights term
    (the same never-materialize discipline as test_13b_readiness)."""
    import jax
    import jax.numpy as jnp

    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.ops import quant as quant_mod

    dtype = {2: jnp.bfloat16, 4: jnp.float32}[int(dtype_bytes)]
    shapes = jax.eval_shape(
        lambda k: eventchat.init_eventchat_params(cfg, k, dtype),
        jax.random.PRNGKey(0),
    )
    if quant == "int8":
        shapes = {
            **shapes,
            "llama": jax.eval_shape(quant_mod.quantize_llama_params,
                                    shapes["llama"]),
        }
    total = 0
    for leaf in jax.tree_util.tree_leaves(shapes):
        size = 1
        for d in leaf.shape:
            size *= int(d)
        total += size * leaf.dtype.itemsize
    return total


def _grain_round(n: int, grain: int) -> int:
    return ((int(n) + grain - 1) // grain) * grain


def kv_pos_bytes(cfg, kv_quant: bool = False, dtype_bytes: int = 2) -> int:
    """K+V bytes of ONE cache position of ONE row — the unit every
    row/lane/entry estimate multiplies. Mirrors ``llama.init_kv_cache``
    exactly: bf16 stores ``L × 2 × KV × hd`` payload; int8 halves the
    payload and adds one f32 scale per (layer, position, kv-head)."""
    lc = cfg.llama
    hd = lc.resolved_head_dim()
    # A hybrid decoder keeps keys and values in its attention layers only;
    # a decoder with window layers, by position in its global layers only.
    if hasattr(lc, "pattern"):
        layers = lc.count("*")
    elif hasattr(lc, "layer_types"):
        layers = lc.count("full_attention")
    else:
        layers = lc.num_layers
    per_plane = layers * lc.num_kv_heads  # per (k|v) per position
    if kv_quant:
        return 2 * per_plane * (hd * 1 + 4)  # int8 payload + f32 scale
    return 2 * per_plane * hd * dtype_bytes


def fixed_state_bytes(cfg, dtype_bytes: int = 2) -> Tuple[int, int]:
    """(bytes a row, bytes a cache) of state that does not grow with the
    position. Mirrors ``nemotron_h.init_cache``: a recurrent layer's conv
    tail in the served type and its ``h`` in float32, a row; what the
    expert layers last counted (``models/experts.STATS``: 6 int32 a layer),
    a cache. And ``afmoe.init_cache``: a window layer's ring of
    ``sliding_window`` slots of keys and values, a row. (0, 0) for a decoder
    whose whole state is keys and values by position."""
    lc = cfg.llama
    if hasattr(lc, "layer_types"):
        ring = (2 * lc.count("sliding_attention") * lc.sliding_window
                * lc.num_kv_heads * lc.resolved_head_dim() * dtype_bytes)
        return ring, (lc.num_layers - lc.num_dense_layers) * 6 * 4
    if not hasattr(lc, "pattern"):
        return 0, 0
    row = lc.count("M") * (
        (lc.conv_kernel - 1) * lc.conv_channels * dtype_bytes
        + lc.mamba_num_heads * lc.mamba_head_dim * lc.ssm_state_size * 4)
    return row, lc.count("E") * 6 * 4


def _mesh_divisors(cfg, mesh_shape: Optional[Dict[str, int]],
                   batch: int) -> Dict[str, int]:
    """The sharding divisors of the serving layout — delegated to
    ``parallel.serving.serving_divisors`` so the capacity model and the
    placement code can never drift (lazy import: the jax-heavy module
    only loads when a mesh shape is actually given)."""
    if not mesh_shape:
        return {"batch": 1, "kv_heads": 1, "weights": 1}
    from eventgpt_tpu.parallel.serving import serving_divisors

    return serving_divisors(cfg.llama.num_kv_heads, mesh_shape, batch)


def estimate(cfg, *, max_batch: int, max_len: int, kv_quant: bool = False,
             dtype_bytes: int = 2, speculative: int = 0,
             prefill_budget: int = 0, prefill_lane_chunk: int = 0,
             lane_bucket: Optional[int] = None,
             prefix_cache_bytes: int = 0, weights_bytes: int = 0,
             vocab: Optional[int] = None,
             mesh_shape: Optional[Dict[str, int]] = None,
             kv_layout: str = "dense", kv_pool_blocks: int = 0,
             kv_block_size: int = 0) -> Dict[str, Any]:
    """Static capacity model: closed-form component bytes for one
    ``ContinuousBatcher`` from its config — what the server WILL hold
    resident, before it is ever built. Mirrors the constructor's own
    arithmetic (grain-rounded ``max_len``, lane cap/chunk policy,
    unquantized lane cache) so ``tests/test_memory_ledger.py`` can hold
    it byte-exact against the live buffers.

    ``weights_bytes``: the weight-tree term, supplied by the caller
    (``params_bytes`` for a live tree, ``abstract_params_bytes`` for a
    never-materialized one) — weight layout (quant/fuse/LoRA) is not
    re-derived here. ``mesh_shape`` ({"data": d, "fsdp": f,
    "model": m}) applies the serving sharding divisors and adds a
    ``per_device`` view — the 13B-over-a-pod fit check."""
    from eventgpt_tpu.constants import SEQ_BUCKET

    grain = 2 * SEQ_BUCKET
    max_len = _grain_round(max_len, grain)
    pos_bytes = kv_pos_bytes(cfg, kv_quant, dtype_bytes)
    row_bytes = max_len * pos_bytes
    vocab = int(vocab if vocab is not None else cfg.llama.vocab_size)

    comp: Dict[str, int] = {}
    if weights_bytes:
        comp["weights"] = int(weights_bytes)
    if kv_layout == "paged":
        # Paged layout (ISSUE 12): one block-pool arena — n_blocks
        # blocks of block_size positions per layer/plane, SCRATCH block
        # included — plus the per-row int32 block tables and the (B,)
        # length plane. Mirrors serve's constructor arithmetic exactly
        # (default pool = dense-equivalent capacity + 1 scratch) so the
        # ledger test can hold it byte-exact against the live arena.
        bs = int(kv_block_size) or SEQ_BUCKET
        nbpr = max_len // bs
        n_blocks = int(kv_pool_blocks) or (max_batch * nbpr + 1)
        comp["kv_pool"] = n_blocks * bs * pos_bytes
        comp["kv_block_table"] = max_batch * nbpr * 4 + max_batch * 4
    else:
        # Resident decode cache: B rows (keys and values by position, and
        # a row's fixed state where the decoder has one) + the (B,) int32
        # length plane.
        fixed_row, fixed_cache = fixed_state_bytes(cfg, dtype_bytes)
        comp["kv_cache"] = (max_batch * (row_bytes + fixed_row)
                            + max_batch * 4 + fixed_cache)
    # Per-row next-token logits carry (f32 by construction).
    comp["logits"] = max_batch * vocab * 4
    if speculative:
        # ids_buf (B, max_len) int32 + the carried drafts (B, W-1) int32.
        comp["ids_buf"] = max_batch * max_len * 4
        comp["draft"] = max_batch * max(speculative - 1, 0) * 4
    if prefill_budget > 0:
        # The constructor's lane policy, verbatim: chunk_p =
        # prefill_lane_chunk or min(budget, SEQ_BUCKET); K_cap =
        # budget // chunk_p capped at max_batch. Lane KV is ALWAYS
        # unquantized (the exactness rule), plus the (K, S, D) embeds.
        lane_chunk = int(prefill_lane_chunk) or min(prefill_budget,
                                                    SEQ_BUCKET)
        lane_chunk = max(1, min(lane_chunk, prefill_budget))
        k_cap = max(1, min(prefill_budget // lane_chunk, max_batch))
        s_lane = _grain_round(lane_bucket or grain, grain)
        s_lane = min(s_lane, max_len)
        lane_pos = kv_pos_bytes(cfg, False, dtype_bytes)
        comp["lanes"] = k_cap * s_lane * (
            lane_pos + cfg.llama.hidden_size * dtype_bytes) + k_cap * 4
    if prefix_cache_bytes:
        # The cache's own LRU budget IS its capacity claim (entries are
        # bucket-grain blocks of the same pos_bytes unit).
        comp["prefix_cache"] = int(prefix_cache_bytes)
    total = sum(comp.values())

    out: Dict[str, Any] = {
        "components": comp,
        "total_bytes": total,
        "row_bytes": row_bytes,
        "kv_pos_bytes": pos_bytes,
        "entry_bytes_per_bucket": grain * pos_bytes,
        "max_len": max_len,
    }
    if mesh_shape:
        div = _mesh_divisors(cfg, mesh_shape, max_batch)
        per: Dict[str, int] = {}
        for name, n in comp.items():
            if name == "weights":
                per[name] = n // div["weights"]
            elif name in ("kv_cache", "lanes"):
                # Batch over (data, fsdp) AND kv-heads over model
                # compose multiplicatively (shard_kv_cache's spec).
                per[name] = n // (div["batch"] * div["kv_heads"])
            elif name == "kv_pool":
                # The arena has no batch axis: blocks replicate over
                # the batch axes (any row may read any block), only the
                # KV-head axis shards (shard_kv_cache's paged branch).
                per[name] = n // div["kv_heads"]
            elif name in ("kv_block_table", "logits", "ids_buf", "draft"):
                per[name] = n // div["batch"]
            else:
                per[name] = n // div["kv_heads"] if name == "prefix_cache" \
                    else n
        out["divisors"] = div
        out["per_device"] = per
        out["per_device_total_bytes"] = sum(per.values())
    return out


def compiled_stats(jitted, *args, **kwargs) -> Dict[str, Any]:
    """Compiled-footprint probe: lower + compile the given jit callable
    at the given (concrete or abstract) args and return XLA's
    ``memory_analysis()`` — temp / argument / output / alias /
    generated-code bytes. AOT lowering never executes, so donated
    resident buffers are safe to pass. With the persistent compile
    cache armed (every serve entry point arms it) the compile is a
    cache load, not a fresh XLA run. Backends without memory analysis
    report ``{"unavailable": ...}`` instead of raising — the probe is
    observability, not a dependency."""
    try:
        ma = jitted.lower(*args, **kwargs).compile().memory_analysis()
        if ma is None:
            return {"unavailable": "backend returned no memory_analysis"}
        out = {
            "temp_bytes": int(ma.temp_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:
        return {"unavailable": repr(e)}
    obs_metrics.MEM_COMPILED_TEMP.set(out["temp_bytes"])
    obs_metrics.MEM_COMPILED_ARGUMENT.set(out["argument_bytes"])
    obs_metrics.MEM_COMPILED_OUTPUT.set(out["output_bytes"])
    return out


def device_capacity_bytes() -> int:
    """Device memory limit (``memory_stats()`` of device 0; the TPU reports
    ``bytes_limit``). 0 = the backend reports none (CPU) — the headroom
    guard is inert there without an explicit ``--mem_capacity_mb``. A
    failing ``memory_stats()`` raises: swallowing it would disarm the
    guard on the chip."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0))
