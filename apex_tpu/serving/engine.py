"""ServeEngine — AOT-compiled, bucketed, continuous-batching decode.

The forward-only production path the ROADMAP's open item 3 asks for.
Shape discipline is the whole design: at startup the engine
ahead-of-time compiles (``jax.jit(...).lower(...).compile()``) exactly
ONE prefill executable per (batch-bucket, seq-bucket) pair and ONE
decode executable per batch-bucket, registers every compile with the
:class:`~apex_tpu.telemetry.compile_watch.CompileWatcher`, and from
then on steady-state traffic — whatever its arrival pattern — only
ever *calls* those executables. ``assert_no_recompiles`` around the
serving loop is therefore a hard invariant, not a hope: the compile
count equals the bucket-ladder size and stays flat as traffic varies
(the compile watcher was built for exactly this; see
docs/observability.md).

The decode step reuses the model's own incremental-decode semantics:
``generation.prefill`` / ``generation.decode_step`` vmapped over cache
slots, each slot carrying its own ``cache_index`` so mixed sequence
lengths coexist in one batch (greedy output is token-identical to
``generation.generate`` for the bf16 cache — pinned in
tests/L0/test_serving.py). The KV cache is the slotted store of
:mod:`apex_tpu.serving.kv_cache`: sharded over the data axis,
optionally int8-quantized with dequant-on-read inside the compiled
step.

Resource discipline mirrors the training substrate: cache preallocation
(the dominant HBM cost) runs under ``telemetry.memory.oom_guard``, the
decode step's budget is preflighted before any traffic, and every
decode dispatch goes through ``resilience.guarded_call`` so a real (or
injected) RESOURCE_EXHAUSTED writes a memory post-mortem instead of a
bare traceback. See docs/serving.md for the operational tour.
"""

import dataclasses
import time
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models import generation
from apex_tpu.parallel import compression
from apex_tpu.serving import kv_cache as kvc
from apex_tpu.serving.prefix_cache import PrefixStore
from apex_tpu.telemetry import compile_watch
from apex_tpu.telemetry import memory as tmemory
from apex_tpu.telemetry.registry import get_registry


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving knobs — everything that shapes an executable.

    ``batch_buckets`` is the decode ladder (active sequences pad up to
    the smallest bucket that fits); ``prefill_buckets`` the prompt-
    length ladder (prompts right-pad up to a bucket, the pad positions
    stay masked by the cache's absolute-position attention). The AOT
    compile count is ``len(batch_buckets) * len(prefill_buckets) +
    len(batch_buckets)`` — fixed at startup, flat under any traffic,
    and UNCHANGED by the two serving multipliers below (each swaps an
    executable's body, never grows the ladder).

    ``draft_model`` (+ ``draft_params``) turns every decode dispatch
    into one speculative round: the draft proposes
    ``num_draft_tokens`` greedily, the target verifies the whole
    window in ONE chunked forward with a fused in-graph sampling /
    acceptance / rollback epilogue (no host round-trip between draft
    and verify), and each slot emits its own accepted prefix plus one
    target token — greedy-only (``temperature`` must stay 0.0; the
    token-exactness contract of ``speculative_generate``).

    ``prefix_cache`` keeps a per-engine host-side
    :class:`~apex_tpu.serving.prefix_cache.PrefixStore`: a prompt
    whose prefix was prefilled before seeds its slot's KV rows from
    the cached copy and prefills only the suffix bucket.
    """

    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    prefill_buckets: Tuple[int, ...] = (16, 32, 64)
    num_slots: int = 8
    cache_mode: str = "bf16"            # "bf16" | "int8"
    block_size: int = compression.BLOCK_SIZE
    temperature: float = 0.0            # 0 = greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    data_axis: str = "data"             # mesh axis the slot dim shards over
    # mesh axis the model shards over in tensor-parallel serving. Must
    # stay "tp": GPTModel hardwires its collectives to axis name "tp",
    # and an unbound axis makes those psums silently vanish (axis size
    # 1) — wrong results, not an error — so the engine validates the
    # name loudly instead of accepting an alias.
    model_axis: str = "tp"
    donate: bool = True                 # donate the store through the step
    preflight: bool = True
    preflight_strict: bool = False
    # speculative decode (None = plain one-token decode)
    draft_model: Any = dataclasses.field(default=None, repr=False,
                                         compare=False)
    draft_params: Any = dataclasses.field(default=None, repr=False,
                                          compare=False)
    num_draft_tokens: int = 4
    # cross-request prefix cache (host-side, per engine/replica)
    prefix_cache: bool = False
    prefix_min_len: int = 4
    prefix_max_entries: int = 8
    # route the k+1-position verify attention through the fused
    # flash-window kernel (kernels/fused_cc.window_attention) when the
    # fused_cc gate is live; False pins the einsum formulation for
    # this engine's traced executables regardless of the gate
    fused_verify: bool = True


def kv_payload_crc(payload):
    """Recompute a migration payload's checksum from its contents —
    the verification side of :meth:`ServeEngine.extract_kv_state`.
    Folds the target rows, the draft rows (when present), and the
    fill length into one crc32; any flipped byte anywhere in the
    pytree (or a tampered length) changes the result."""
    crc = kvc.payload_checksum(payload["rows"])
    if payload.get("draft_rows") is not None:
        crc = kvc.payload_checksum(payload["draft_rows"], crc)
    return kvc.payload_checksum(
        [np.asarray(int(payload["length"]), np.int64)], crc)


class ServeEngine:
    """AOT-compiled prefill/decode over a slotted KV cache.

    The engine owns the device store and the compiled executables; it
    is deliberately ignorant of *requests* — admission, eviction, and
    latency accounting live in
    :class:`~apex_tpu.serving.scheduler.Scheduler` (which
    :meth:`serve` constructs for the common case). ``slot_ids`` in the
    host API are plain Python ints; padding a bucket uses caller-
    provided FREE slots (distinct ids — a duplicate scatter would
    collide), which the scheduler always has by construction.
    """

    def __init__(self, model, params, config: ServeConfig = None, *,
                 mesh=None, watcher=None, registry=None, name=None):
        from apex_tpu.transformer.parallel_state import (
            get_tensor_model_parallel_world_size,
        )

        tp = get_tensor_model_parallel_world_size()
        self._tp = int(tp)
        if not getattr(model, "decode", False):
            raise ValueError("ServeEngine needs a model built with "
                             "decode=True")
        config = config or ServeConfig()
        if not config.batch_buckets or not config.prefill_buckets:
            raise ValueError("empty bucket ladder")
        bb = tuple(sorted(set(int(b) for b in config.batch_buckets)))
        sb = tuple(sorted(set(int(s) for s in config.prefill_buckets)))
        if bb[-1] > config.num_slots:
            raise ValueError(
                f"largest batch bucket ({bb[-1]}) exceeds num_slots "
                f"({config.num_slots}) — a bucket gathers distinct slots")
        limit = model.config.max_position_embeddings
        if sb[-1] > limit:
            raise ValueError(
                f"largest prefill bucket ({sb[-1]}) exceeds "
                f"max_position_embeddings ({limit})")
        if tp > 1:
            # tensor-parallel serving: the model was built under
            # parallel_state tp=m, so its cache template is the LOCAL
            # per-rank layout and its collectives name axis "tp" — the
            # engine's job is to give that axis a mesh to live on and
            # shard the store's head dimension over it.
            if mesh is None:
                raise ValueError(
                    f"tensor parallel serving (tp={tp}) needs a (data, "
                    f"model) mesh — pass mesh=Mesh(devs.reshape(1, "
                    f"{tp}), ('{config.data_axis}', "
                    f"'{config.model_axis}'))")
            if config.model_axis != "tp":
                raise ValueError(
                    f"model_axis ({config.model_axis!r}) must be 'tp': "
                    f"the model's collectives are hardwired to that "
                    f"axis name, and an unbound axis would silently "
                    f"skip every psum (axis size 1) instead of failing")
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if sizes.get(config.model_axis) != tp:
                raise ValueError(
                    f"mesh axis {config.model_axis!r} has size "
                    f"{sizes.get(config.model_axis)} but "
                    f"parallel_state says tp={tp} — the mesh must "
                    f"match the process-group layout the model was "
                    f"built under")
            if sizes.get(config.data_axis, 1) != 1:
                raise ValueError(
                    f"a TP-sharded engine serves one replica: the "
                    f"{config.data_axis!r} axis must have size 1 "
                    f"(got {sizes.get(config.data_axis)}) — scale out "
                    f"with fleet replicas, not a wide data axis")
        elif mesh is not None and config.num_slots % mesh.devices.size:
            raise ValueError(
                f"num_slots ({config.num_slots}) must divide evenly "
                f"over the {mesh.devices.size}-device mesh")
        self._spec_decode = config.draft_model is not None
        if self._spec_decode:
            draft = config.draft_model
            if config.draft_params is None:
                raise ValueError("draft_model needs draft_params")
            if not getattr(draft, "decode", False):
                raise ValueError("draft_model must be built with "
                                 "decode=True")
            if draft.config.vocab_size != model.config.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft.config.vocab_size}) != target "
                    f"vocab ({model.config.vocab_size}): the models "
                    f"must share a tokenizer")
            if config.temperature:
                raise ValueError(
                    "speculative serving is greedy-only (temperature "
                    "must be 0.0): verification proves token-exactness "
                    "against target argmax, which sampling breaks")
            if config.num_draft_tokens < 1:
                raise ValueError(
                    f"num_draft_tokens ({config.num_draft_tokens}) "
                    f"must be >= 1")
            limit = min(limit, draft.config.max_position_embeddings)
            if sb[-1] > limit:
                raise ValueError(
                    f"largest prefill bucket ({sb[-1]}) exceeds the "
                    f"draft model's position budget ({limit})")
        self.model = model
        self.config = dataclasses.replace(config, batch_buckets=bb,
                                          prefill_buckets=sb)
        self._prefix = bool(config.prefix_cache)
        # per-caller attribution on a possibly-shared store: the
        # fleet swaps in one fleet-scoped PrefixStore via
        # adopt_prefix_store, and each engine generation's distinct
        # name keeps its hit columns separate from its predecessors'
        self._scope = name or "engine"
        self.prefix_store = PrefixStore(
            max_entries=config.prefix_max_entries,
            min_len=config.prefix_min_len) if self._prefix else None
        self.last_prefill_hits = []
        # ``name`` prefixes every AOT registration with the compile
        # watcher: two fleet replicas compile the same ladder with
        # DIFFERENT NamedShardings (distinct device slices), so without
        # distinct names the second registration would be flagged as a
        # signature-diffed recompile — and a respawned replica must use
        # a fresh name for the same reason (serving.fleet appends the
        # generation).
        self.name = name
        self.mesh = mesh
        self.max_len = limit
        self._watcher = watcher if watcher is not None \
            else compile_watch.get_watcher()
        self._registry = registry
        self.spec = kvc.KVCacheSpec(model, config.num_slots,
                                    mode=config.cache_mode,
                                    block_size=config.block_size)
        self.draft_spec = kvc.KVCacheSpec(
            config.draft_model, config.num_slots,
            mode=config.cache_mode, block_size=config.block_size) \
            if self._spec_decode else None

        # --- allocate the store(s) (THE serving HBM cost) under the OOM
        # post-mortem handler, then commit shardings ---------------------
        labels = {"params": params}
        dstore = dparams = None
        self._row_shardings = {}
        with tmemory.oom_guard(registry=registry, labels=labels):
            if self._tp > 1:
                # TP placement: params stacked [tp, ...] in tp_split's
                # column/row-parallel layout and sharded over the model
                # axis; the store allocated as host numpy GLOBAL zeros
                # and device_put against the per-leaf spec tree — a
                # traced per-rank allocate would register a compile
                # OUTSIDE the AOT ladder and poison the fleet's
                # recompile accounting on respawn.
                from jax.sharding import NamedSharding, PartitionSpec
                from apex_tpu.models.tp_split import split_params_for_tp

                def shardings(pspecs):
                    return jax.tree_util.tree_map(
                        lambda ps: NamedSharding(mesh, ps), pspecs,
                        is_leaf=lambda l: isinstance(l, PartitionSpec))

                ax = config.model_axis
                self._replicated = NamedSharding(mesh, PartitionSpec())
                self._param_sharding = NamedSharding(
                    mesh, PartitionSpec(ax))
                self._sharded = shardings(
                    self.spec.store_pspecs(config.data_axis, ax))
                store = jax.device_put(
                    self.spec.host_global_store(self._tp), self._sharded)
                params = jax.device_put(
                    split_params_for_tp(model.config, params, self._tp),
                    self._param_sharding)
                self._row_shardings["target"] = shardings(
                    self.spec.row_pspecs(ax, lead=1))
                if self._spec_decode:
                    self._draft_sharded = shardings(
                        self.draft_spec.store_pspecs(config.data_axis,
                                                     ax))
                    dstore = jax.device_put(
                        self.draft_spec.host_global_store(self._tp),
                        self._draft_sharded)
                    dparams = jax.device_put(
                        split_params_for_tp(config.draft_model.config,
                                            config.draft_params,
                                            self._tp),
                        self._param_sharding)
                    self._row_shardings["draft"] = shardings(
                        self.draft_spec.row_pspecs(ax, lead=1))
            else:
                store = self.spec.allocate()
                if self._spec_decode:
                    dstore = self.draft_spec.allocate()
                    dparams = config.draft_params
                if mesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec

                    self._sharded = NamedSharding(
                        mesh, PartitionSpec(config.data_axis))
                    self._replicated = NamedSharding(mesh,
                                                     PartitionSpec())
                    store = jax.device_put(store, self._sharded)
                    params = jax.device_put(params, self._replicated)
                    if self._spec_decode:
                        dstore = jax.device_put(dstore, self._sharded)
                        dparams = jax.device_put(dparams,
                                                 self._replicated)
                else:
                    self._sharded = self._replicated = None
        self._store = store
        self._draft_store = dstore
        self._params = params
        self._draft_params = dparams
        self._key0 = jax.random.PRNGKey(0)
        self._step_counter = 0
        self._decode_calls = 0
        self.decode_retries_total = 0
        self._zero_rows_np = {}      # (bucket, which) -> host zero stack
        self._zero_rows_dev = {}     # same, pre-device-put (miss fast path)
        # census attribution for every OOM post-mortem from here on:
        # a serve-time death names KV-cache slots (and the draft
        # model's, when speculating), not anonymous buffers
        labels.update(self.census_labels())

        # --- AOT compile the whole ladder, registered with the watcher --
        # The ladder SIZE is invariant to the serving multipliers: a
        # draft model swaps each decode executable's body for the
        # fused draft-k -> verify -> rollback round, the prefix cache
        # swaps each prefill's for the seeded suffix form — every
        # draft/verify executable registers under the engine's
        # ``name=`` prefix like the rest of the ladder, so fleet
        # respawn recompile accounting stays exact.
        self._decode_exec = {}
        self._prefill_exec = {}
        self.aot_compile_seconds = 0.0
        decode_lowered = None
        aot = f"{name}/serve" if name else "serve"
        decode_body = self._spec_decode_fn if self._spec_decode \
            else self._decode_fn
        prefill_body = self._prefill_fn
        if self._tp > 1:
            # manual-SPMD ladder: every executable is jit(shard_map)
            # over the (data=1, tp=m) mesh — the model's 'tp' psums
            # bind inside, the store stays head-sharded through the
            # step, and nothing ever lowers through GSPMD propagation
            decode_body = self._tp_decode_body()
            prefill_body = self._tp_prefill_body()
        decode_tag = "spec_decode" if self._spec_decode else "decode"
        prefill_tag = "seeded_prefill" if self._prefix else "prefill"
        donate = ((0, 1) if self._spec_decode else (0,)) \
            if config.donate else ()
        from apex_tpu.kernels import fused_cc as _fused_cc

        with tmemory.oom_guard(registry=registry, labels=labels), \
                _fused_cc.verify_scope(config.fused_verify):
            for b in self.config.batch_buckets:
                args = self._decode_args(
                    self._ids_aval(b), self._ids_aval(b), self._key0,
                    self._put(np.int32(-1)))
                lowered = jax.jit(
                    decode_body, donate_argnums=donate).lower(*args)
                self._decode_exec[b] = self._compile(
                    lowered,
                    f"{aot}/{config.cache_mode}/{decode_tag}_b{b}", args)
                decode_lowered = lowered
                for s in self.config.prefill_buckets:
                    pargs = self._prefill_args(
                        self._ids_aval(b), self._tokens_aval(b, s),
                        self._ids_aval(b), self._ids_aval(b),
                        self._seed_rows_dev(b, "target"),
                        self._seed_rows_dev(b, "draft"), self._key0)
                    plow = jax.jit(
                        prefill_body, donate_argnums=donate
                    ).lower(*pargs)
                    self._prefill_exec[(b, s)] = self._compile(
                        plow,
                        f"{aot}/{config.cache_mode}/{prefill_tag}"
                        f"_b{b}_s{s}", pargs)
        if config.temperature:
            # warm the host-side PRNG fold so the first sampled step
            # inside an assert_no_recompiles window compiles nothing
            jax.random.fold_in(self._key0, 0).block_until_ready()

        # --- HBM accounting: the decode step IS the steady state --------
        self.memory_report = None
        if config.preflight and decode_lowered is not None:
            self.memory_report = tmemory.report_from_lowered(
                decode_lowered, registry=registry, name="serve/decode")
            rep = self.memory_report
            if rep is not None and rep.get("headroom_frac") is not None \
                    and rep["headroom_frac"] < 0.0:
                msg = (f"serve decode step peak "
                       f"{rep['peak_bytes'] / 1e9:.2f} GB exceeds HBM "
                       f"capacity {rep['capacity_bytes'] / 1e9:.2f} GB "
                       f"— shrink num_slots, the bucket ladder, or "
                       f"switch cache_mode='int8'")
                if config.preflight_strict:
                    raise tmemory.MemoryBudgetError(msg)
                import warnings

                warnings.warn(msg, stacklevel=2)

        reg = self._reg()
        if reg.enabled:
            reg.gauge("serve/kv_cache_bytes").set(self.kv_cache_bytes())
            reg.counter("serve/aot_compiles").inc(self.compile_count)
            reg.event("serve", "engine_start",
                      engine=name,
                      batch_buckets=list(self.config.batch_buckets),
                      prefill_buckets=list(self.config.prefill_buckets),
                      num_slots=config.num_slots,
                      cache_dtype=self.spec.cache_dtype_name(),
                      kv_cache_bytes=self.kv_cache_bytes(),
                      compile_count=self.compile_count,
                      speculative=self._spec_decode,
                      num_draft_tokens=(config.num_draft_tokens
                                        if self._spec_decode else None),
                      draft_kv_cache_bytes=(self.draft_kv_cache_bytes()
                                            if self._spec_decode
                                            else None),
                      prefix_cache=self._prefix,
                      aot_compile_seconds=round(
                          self.aot_compile_seconds, 4))

    # -- small helpers -----------------------------------------------------

    def _reg(self):
        return self._registry or get_registry()

    def _compile(self, lowered, name, args):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        self.aot_compile_seconds += dt
        # lowered rides along so APEX_TPU_HLO_LINT=1 lints every ladder
        # executable (apex_tpu.analysis) without a second trace
        self._watcher.record_aot(name, args, seconds=dt, lowered=lowered)
        return compiled

    def _ids_aval(self, b):
        return self._put(np.zeros((b,), np.int32))

    def _tokens_aval(self, b, s):
        return self._put(np.zeros((b, s), np.int32))

    def _put(self, x):
        x = np.asarray(x)
        if self._replicated is not None:
            return jax.device_put(x, self._replicated)
        return jnp.asarray(x)

    def _key(self):
        if not self.config.temperature:
            return self._key0
        self._step_counter += 1
        return jax.random.fold_in(self._key0, self._step_counter)

    # -- argument assembly (AOT lowering and host dispatch share it) -------

    def _decode_args(self, slot_ids, tokens, key, poison):
        if self._spec_decode:
            return (self._store, self._draft_store, self._params,
                    self._draft_params, slot_ids, tokens, key, poison)
        return (self._store, self._params, slot_ids, tokens, key,
                poison)

    def _prefill_args(self, slot_ids, tokens, true_len, start,
                      prefix_rows, draft_prefix_rows, key):
        args = [self._store]
        if self._spec_decode:
            args.append(self._draft_store)
        args.append(self._params)
        if self._spec_decode:
            args.append(self._draft_params)
        args += [slot_ids, tokens, true_len]
        if self._prefix:
            args += [start, prefix_rows]
            if self._spec_decode:
                args.append(draft_prefix_rows)
        args.append(key)
        return tuple(args)

    def _host_zero_rows(self, b, which):
        """Host zero seed stack ``[b, ...]`` in store layout, cached
        per bucket — the prefix-cache miss filler (and the template
        the hit path stacks entries into)."""
        if not self._prefix or (which == "draft"
                                and not self._spec_decode):
            return None
        key = (b, which)
        if key not in self._zero_rows_np:
            spec = self.spec if which == "target" else self.draft_spec
            zero = spec.host_zero_row(tp=self._tp)
            self._zero_rows_np[key] = jax.tree_util.tree_map(
                lambda l: np.zeros((b,) + l.shape, l.dtype), zero)
        return self._zero_rows_np[key]

    def _seed_rows_dev(self, b, which):
        """Pre-placed all-miss seed stack (device arrays are
        immutable, so one placement serves every miss-only prefill)."""
        rows = self._host_zero_rows(b, which)
        if rows is None:
            return None
        key = (b, which)
        if key not in self._zero_rows_dev:
            self._zero_rows_dev[key] = self._put_rows(rows, which)
        return self._zero_rows_dev[key]

    def _put_rows(self, rows, which):
        """Place a [b]-stacked CANONICAL seed-row tree: in TP mode the
        K/V groups axis shards over the model axis (each rank receives
        exactly its head slice — the reshard half of the migration
        pair); otherwise replicated like every other host operand."""
        if self._tp > 1:
            return jax.device_put(rows, self._row_shardings[which])
        return jax.tree_util.tree_map(self._put, rows)

    # -- tensor-parallel ladder bodies (jit(shard_map) manual SPMD) --------

    def _tp_decode_body(self):
        """The decode body wrapped in one ``shard_map`` over the whole
        step: store rows arrive head-sharded, params arrive as each
        rank's stacked slice (unstacked inside, the
        ``tensor_parallel_generate`` idiom), and the model's own 'tp'
        collectives — attention/MLP psums, the vocab gather before
        sampling — bind against the mesh axis. Everything downstream
        of the gather is rank-identical (shared key), so tokens and
        flags leave as replicated outputs."""
        from jax.sharding import PartitionSpec as P

        cfg = self.config
        ax = cfg.model_axis
        store_ps = self.spec.store_pspecs(cfg.data_axis, ax)
        unstack = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)  # noqa: E731
        if self._spec_decode:
            dstore_ps = self.draft_spec.store_pspecs(cfg.data_axis, ax)

            def body(store, dstore, params, dparams, slot_ids, tokens,
                     key, poison):
                return self._spec_decode_fn(
                    store, dstore, unstack(params), unstack(dparams),
                    slot_ids, tokens, key, poison)

            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(store_ps, dstore_ps, P(ax), P(ax), P(), P(),
                          P(), P()),
                out_specs=(store_ps, dstore_ps, P(), P(), P()),
                check_vma=False)

        def body(store, params, slot_ids, tokens, key, poison):
            return self._decode_fn(store, unstack(params), slot_ids,
                                   tokens, key, poison)

        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(store_ps, P(ax), P(), P(), P(), P()),
            out_specs=(store_ps, P(), P()),
            check_vma=False)

    def _tp_prefill_body(self):
        """The prefill body under the same ``shard_map`` treatment.
        Seed rows cross the boundary in CANONICAL layout and the
        in_specs slice each rank's head shard out (so entries cached
        by an engine of a different tp size seed here unchanged); the
        raw-row outputs reassemble to canonical through the matching
        out_specs — together the consolidate/reshard pair the
        KV-state migration is built on."""
        from jax.sharding import PartitionSpec as P

        cfg = self.config
        ax = cfg.model_axis
        store_ps = self.spec.store_pspecs(cfg.data_axis, ax)
        row_ps = self.spec.row_pspecs(ax, lead=1)
        unstack = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)  # noqa: E731
        in_specs = [store_ps]
        out_specs = [store_ps]
        if self._spec_decode:
            dstore_ps = self.draft_spec.store_pspecs(cfg.data_axis, ax)
            drow_ps = self.draft_spec.row_pspecs(ax, lead=1)
            in_specs.append(dstore_ps)
            out_specs.append(dstore_ps)
        in_specs.append(P(ax))
        if self._spec_decode:
            in_specs.append(P(ax))
        in_specs += [P(), P(), P()]         # slot_ids, tokens, true_len
        if self._prefix:
            in_specs += [P(), row_ps]       # start, prefix_rows
            if self._spec_decode:
                in_specs.append(drow_ps)
        in_specs.append(P())                # key
        out_specs.append(P())               # first sampled token
        if self._prefix:
            out_specs.append(row_ps)
            if self._spec_decode:
                out_specs.append(drow_ps)

        def body(*args):
            it = iter(args)
            a2 = [next(it)]
            if self._spec_decode:
                a2.append(next(it))
            a2.append(unstack(next(it)))
            if self._spec_decode:
                a2.append(unstack(next(it)))
            a2.extend(it)
            return self._prefill_fn(*a2)

        return jax.shard_map(body, mesh=self.mesh,
                             in_specs=tuple(in_specs),
                             out_specs=tuple(out_specs),
                             check_vma=False)

    @property
    def compile_count(self):
        """AOT executables compiled at startup — the serving compile
        budget, by construction flat under any traffic shape (the
        speculative and seeded executables REPLACE ladder entries,
        they never add any)."""
        return len(self._decode_exec) + len(self._prefill_exec)

    @property
    def spec_enabled(self):
        """True when decode dispatches run the speculative round
        (multi-token results — the scheduler branches on this)."""
        return self._spec_decode

    @property
    def decode_headroom(self):
        """Cache positions a decode dispatch may write BEYOND the
        emitted tokens: the speculative window overshoots by up to
        ``num_draft_tokens``, so admission must keep ``prompt +
        max_new + headroom`` inside the position budget."""
        return self.config.num_draft_tokens if self._spec_decode else 0

    @property
    def prefix_hits(self):
        """THIS engine's hits — per-scope numbers, so a fleet-shared
        store still reports each replica's own column truthfully."""
        return self.prefix_store.scope_stats(self._scope)["hits"] \
            if self._prefix else 0

    @property
    def prefix_lookups(self):
        return self.prefix_store.scope_stats(self._scope)["lookups"] \
            if self._prefix else 0

    @property
    def prefix_hit_tokens(self):
        return self.prefix_store.scope_stats(
            self._scope)["hit_tokens"] if self._prefix else 0

    def adopt_prefix_store(self, store):
        """Swap in a shared (fleet-scoped) :class:`PrefixStore`. Host-
        only and compile-free, so the fleet calls it right after
        construction; per-scope accounting keeps this engine's hit
        columns separate on the shared store. Returns the store."""
        if not self._prefix:
            raise ValueError(
                "engine was built without prefix_cache=True — there "
                "is no seeded-prefill ladder to serve a shared store")
        self.prefix_store = store
        return store

    def kv_cache_bytes(self):
        return self.spec.total_bytes()

    def draft_kv_cache_bytes(self):
        return self.draft_spec.total_bytes() if self._spec_decode else 0

    def census_labels(self):
        """OOM post-mortem attribution (`live_buffer_census` matches
        leaves by identity): rebuilt per call because donation replaces
        the store arrays on every dispatch — a serve-time census must
        name the CURRENT KV-cache slots, not dead buffers. The draft
        ladder's buffers are first-class here: a speculative engine's
        OOM names the draft store and draft weights next to the
        target's."""
        labels = {"params": self._params, "kv_cache": self._store}
        if self._spec_decode:
            labels["draft_params"] = self._draft_params
            labels["kv_cache_draft"] = self._draft_store
        return labels

    def slot_lengths(self):
        """Host copy of the per-slot fill levels (one tiny fetch)."""
        return np.asarray(kvc.store_lengths(self._store))

    def seed_row_template(self, which="target"):
        """The CANONICAL (cross-rank) host row layout this engine
        seeds slots from — the shape/dtype contract a migration
        payload's rows must satisfy. tp-independent by construction:
        a tp=m engine's local groups axis times m is exactly the tp=1
        model layout, so engines of any TP size agree on it."""
        spec = self.spec if which == "target" else self.draft_spec
        return spec.host_zero_row(tp=self._tp) if spec is not None \
            else None

    def extract_kv_state(self, slot_ids):
        """Device-get each slot's KV state and consolidate it into a
        checksummed host payload — the donor half of constant-cost
        migration. Per slot: fetch the (possibly head-sharded) store
        rows, consolidate them to CANONICAL raw model-layout rows
        (per-rank int8 blocks dequantize and concatenate in head
        order — ``KVCacheSpec.consolidate_host_rows``), fetch the
        draft rows the same way on a speculative engine, and fold
        rows + fill length into a crc32 (:func:`kv_payload_crc`).

        Returns ``{slot: {"slot", "length", "tp", "cache_mode",
        "rows", "draft_rows", "crc"}}``. Call AFTER
        ``Scheduler.extract_unfinished`` (slot release only forgets
        the id — the rows stay resident) and BEFORE anything prefills
        into the freed slots."""
        lengths = self.slot_lengths()
        out = {}
        for slot in slot_ids:
            slot = int(slot)
            rows = jax.tree_util.tree_map(
                lambda l: np.asarray(jax.device_get(l[slot])),
                self._store)
            canon = self.spec.consolidate_host_rows(rows, tp=self._tp)
            dcanon = None
            if self._spec_decode:
                drows = jax.tree_util.tree_map(
                    lambda l: np.asarray(jax.device_get(l[slot])),
                    self._draft_store)
                dcanon = self.draft_spec.consolidate_host_rows(
                    drows, tp=self._tp)
            payload = {
                "slot": slot,
                "length": int(lengths[slot]),
                "tp": self._tp,
                "cache_mode": self.config.cache_mode,
                "rows": canon,
                "draft_rows": dcanon,
            }
            payload["crc"] = kv_payload_crc(payload)
            out[slot] = payload
        return out

    def _pick_bucket(self, ladder, n, what):
        for b in ladder:
            if n <= b:
                return b
        raise ValueError(f"{what} ({n}) exceeds the largest bucket "
                         f"({ladder[-1]})")

    # -- the compiled step bodies (pure; AOT-lowered at startup) -----------

    def _sample(self, logits, key):
        cfg = self.config
        return generation.sample_logits(
            logits, key, cfg.temperature, cfg.top_k, cfg.top_p
        ).astype(jnp.int32)

    def _unpack_prefill(self, args):
        it = iter(args)
        store = next(it)
        dstore = next(it) if self._spec_decode else None
        params = next(it)
        dparams = next(it) if self._spec_decode else None
        slot_ids, tokens, true_len = next(it), next(it), next(it)
        start = prefix_rows = dprefix_rows = None
        if self._prefix:
            start, prefix_rows = next(it), next(it)
            if self._spec_decode:
                dprefix_rows = next(it)
        return (store, dstore, params, dparams, slot_ids, tokens,
                true_len, start, prefix_rows, dprefix_rows, next(it))

    def _prefill_one_model(self, model, params, spec, tokens, true_len,
                           start, prefix_rows):
        """vmapped per-slot prefill for one model (target or draft):
        seeds from the passed FULL-PRECISION prefix rows (prefix mode
        — the row's ``cache_index`` rolls to the cut, so a shorter
        cached prefix is just a smaller index; positions past it stay
        resident but masked) or from a zero row, prefills the (suffix)
        tokens at offset positions, and rolls ``cache_index`` to the
        true end.

        Exactness hinges on the seeds being raw (model-layout, never
        dequantized): the suffix forward then attends over EXACTLY the
        prefix K/V a cold full prefill would have computed, and
        re-quantizing the raw prefix reproduces the cold store's int8
        blocks bit-for-bit (same values, same deterministic grid).
        Seeding from dequantized int8 instead would perturb every
        suffix K/V through the lossy prefix — enough to flip a
        near-tie argmax many tokens later (caught by the 8-device
        verify probe).

        Returns ``(store_rows, raw_rows, last_logits)`` — the
        quantized rows for the store scatter and the raw merged rows
        the host caches for future hits."""
        s = tokens.shape[1]

        def one(tok_row, n, st, prow):
            if self._prefix:
                base = generation._set_cache_index(prow, st)
                pos = (st + jnp.arange(s))[None, :]
                end = st + n
            else:
                base = kvc.zero_row(spec.template)
                pos = jnp.arange(s)[None, :]
                end = n
            cache, logits = generation.prefill(
                model, params, base, tok_row[None, :], pos,
                full_logits=True)
            last = logits[0, n - 1]                  # [vocab], true last
            return generation._set_cache_index(cache, end), last

        if self._prefix:
            raw, last_logits = jax.vmap(one)(tokens, true_len, start,
                                             prefix_rows)
        else:
            raw, last_logits = jax.vmap(
                lambda t, n: one(t, n, None, None))(tokens, true_len)
        return spec.quantize_rows(raw), raw, last_logits

    def _prefill_fn(self, *args):
        """Admit a bucket: per-slot prefill at padded length S,
        cache_index rolled back to each row's true end (pad positions
        stay resident but masked — the speculative-decode rollback
        trick), first token sampled from the true last position's
        TARGET logits. With a draft model the draft cache prefills the
        same tokens in the same executable (lockstep fill levels);
        with the prefix cache the merged store-layout rows ride out as
        extra outputs so the host can cache them for future hits."""
        (store, dstore, params, dparams, slot_ids, tokens, true_len,
         start, prefix_rows, dprefix_rows, key) = \
            self._unpack_prefill(args)
        rows, raw, last_logits = self._prefill_one_model(
            self.model, params, self.spec, tokens, true_len, start,
            prefix_rows)
        first = self._sample(last_logits, key)
        store = jax.tree_util.tree_map(
            lambda st, r: st.at[slot_ids].set(r), store, rows)
        out = [store]
        if self._spec_decode:
            drows, draw, _ = self._prefill_one_model(
                self.config.draft_model, dparams, self.draft_spec,
                tokens, true_len, start, dprefix_rows)
            dstore = jax.tree_util.tree_map(
                lambda st, r: st.at[slot_ids].set(r), dstore, drows)
            out.append(dstore)
        out.append(first)
        if self._prefix:
            out.append(raw)
            if self._spec_decode:
                out.append(draw)
        return tuple(out)

    def _decode_fn(self, store, params, slot_ids, tokens, key,
                   poison_slot):
        """One continuous-batching decode step over a slot bucket:
        gather rows, dequantize on read, run the model's own decode
        attention per slot at its own length, re-quantize ONLY the
        appended position, scatter back, sample.

        Per-slot quarantine rides in the same executable: a per-slot
        finite flag is derived from each row's logits (vmapped with
        the step — no executable beyond the ladder) and a non-finite
        row scatters ZEROED rows back (its KV and ``cache_index``
        reset in-graph) while sampling the pad token; healthy rows are
        untouched. ``poison_slot`` is the fault injector's traced i32
        handle (-1 = identity): ``faults.inject_slot_nan`` poisons one
        named slot's logits without changing the compiled program."""
        rows = jax.tree_util.tree_map(lambda l: l[slot_ids], store)
        model_rows = self.spec.materialize_rows(rows)
        lengths = kvc.store_lengths(model_rows)

        def one(cache_row, tok, n):
            cache_row = generation._set_cache_index(cache_row, n)
            cache_row, logits = generation.decode_step(
                self.model, params, cache_row, tok[None, None],
                jnp.full((1, 1), n, jnp.int32))
            return cache_row, logits[0]

        new_rows, logits = jax.vmap(one)(model_rows, tokens, lengths)
        logits = jnp.where(
            (slot_ids == poison_slot)[:, None],
            jnp.asarray(jnp.nan, logits.dtype), logits)
        finite = jnp.all(jnp.isfinite(
            logits.astype(jnp.float32)), axis=-1)
        nxt = self._sample(logits, key)
        nxt = jnp.where(finite, nxt,
                        jnp.asarray(self.config.pad_token_id, nxt.dtype))
        updated = self.spec.update_rows_at(rows, new_rows, lengths)
        b = finite.shape[0]

        def keep(u):
            f = finite.reshape((b,) + (1,) * (u.ndim - 1))
            return jnp.where(f, u, jnp.zeros_like(u))

        updated = jax.tree_util.tree_map(keep, updated)
        store = jax.tree_util.tree_map(
            lambda st, r: st.at[slot_ids].set(r), store, updated)
        return store, nxt, finite

    def _spec_decode_fn(self, store, dstore, params, dparams, slot_ids,
                        tokens, key, poison_slot):
        """One speculative continuous-batching round over a slot
        bucket — the fused draft -> verify -> accept -> rollback
        epilogue in ONE executable (no host round-trip between draft
        and verification):

        per slot (vmapped, each at its own fill level ``n``): the
        draft greedily proposes ``k`` tokens through its own cache
        (plus the completion feed, so a full accept leaves no hole);
        the target verifies the whole ``[last, d_1..d_k]`` window in
        one chunked forward (:func:`generation.verify_step` — the same
        body ``speculative_generate`` runs); the slot emits its
        longest matching prefix plus one target token (correction on
        mismatch, bonus on full accept) — per-slot MIXED acceptance,
        no batch minimum — and both caches roll their ``cache_index``
        back to ``n + accepted + 1``: rejected positions stay resident
        but masked (the trick this engine's prefill was built on)
        until the next round overwrites them. int8 stores re-quantize
        exactly the ``k + 1``-position window; untouched blocks pass
        through bit-identical.

        Per-slot quarantine rides along unchanged: non-finite
        verification logits (or the ``poison_slot`` injection handle)
        zero the slot's rows in BOTH stores and emit one pad token.

        Returns ``(store, dstore, emitted [b, k+1], counts [b],
        finite [b])`` — ``emitted[i, :counts[i]]`` are slot i's
        verified tokens, every one a target argmax over its own
        prefix (token-identical to the plain decode engine)."""
        k = int(self.config.num_draft_tokens)
        draft = self.config.draft_model
        rows = jax.tree_util.tree_map(lambda l: l[slot_ids], store)
        drows = jax.tree_util.tree_map(lambda l: l[slot_ids], dstore)
        model_rows = self.spec.materialize_rows(rows)
        draft_rows = self.draft_spec.materialize_rows(drows)
        lengths = kvc.store_lengths(model_rows)
        poisoned = slot_ids == poison_slot
        pad = jnp.asarray(self.config.pad_token_id, jnp.int32)

        def one(trow, drow, tok, n, bad):
            trow = generation._set_cache_index(trow, n)
            drow = generation._set_cache_index(drow, n)

            def dstep(carry, i):
                dc, t = carry
                dc, lg = generation.decode_step(
                    draft, dparams, dc, t[None, None],
                    jnp.full((1, 1), n + i, jnp.int32))
                nxt = jnp.argmax(
                    lg[0].astype(jnp.float32), -1).astype(jnp.int32)
                return (dc, nxt), nxt

            # k proposals + one completion feed of d_k (the draft
            # cache must hold every position before the next round's
            # feed, full accept included)
            (drow, _), ds = jax.lax.scan(dstep, (drow, tok),
                                         jnp.arange(k + 1))
            d = ds[:k]                                     # [k]
            chunk = jnp.concatenate([tok[None], d])[None, :]
            cpos = (n + jnp.arange(k + 1))[None, :]
            trow, v, logits = generation.verify_step(
                self.model, params, trow, chunk, cpos)
            v, logits = v[0], logits[0]          # [k+1], [k+1, vocab]
            logits = jnp.where(bad, jnp.asarray(jnp.nan, logits.dtype),
                               logits)
            finite = jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
            match = (d == v[:k]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(match))      # accepted draft count
            emit = jnp.where(jnp.arange(k + 1) == a, jnp.take(v, a),
                             jnp.concatenate([d, d[-1:]]))
            emit = jnp.where(finite, emit, pad).astype(jnp.int32)
            count = jnp.where(finite, a + 1, 1).astype(jnp.int32)
            trow = generation._set_cache_index(trow, n + count)
            drow = generation._set_cache_index(drow, n + count)
            return trow, drow, emit, count, finite

        new_rows, new_drows, emit, counts, finite = jax.vmap(one)(
            model_rows, draft_rows, tokens, lengths, poisoned)
        updated = self.spec.update_rows_span(rows, new_rows, lengths,
                                             k + 1)
        dupdated = self.draft_spec.update_rows_span(
            drows, new_drows, lengths, k + 1)
        b = finite.shape[0]

        def keep(u):
            f = finite.reshape((b,) + (1,) * (u.ndim - 1))
            return jnp.where(f, u, jnp.zeros_like(u))

        updated = jax.tree_util.tree_map(keep, updated)
        dupdated = jax.tree_util.tree_map(keep, dupdated)
        store = jax.tree_util.tree_map(
            lambda st, r: st.at[slot_ids].set(r), store, updated)
        dstore = jax.tree_util.tree_map(
            lambda st, r: st.at[slot_ids].set(r), dstore, dupdated)
        return store, dstore, emit, counts, finite

    # -- host API (the scheduler's surface) --------------------------------

    def _padded_ids(self, slot_ids, pad_slot_ids, bucket):
        ids = list(int(i) for i in slot_ids)
        need = bucket - len(ids)
        if need:
            pads = [int(i) for i in (pad_slot_ids or ())
                    if int(i) not in ids][:need]
            if len(pads) < need:
                raise ValueError(
                    f"bucket {bucket} needs {need} pad slot(s) but only "
                    f"{len(pads)} free id(s) were provided — pad ids "
                    f"must be distinct unused slots (a duplicate "
                    f"scatter would collide)")
            ids += pads
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate slot ids in {ids}")
        return ids

    def prefill(self, slot_ids, prompts, *, pad_slot_ids=None):
        """Prefill ``prompts[i]`` (unpadded 1-D int arrays) into
        ``slot_ids[i]`` and return the first generated token per
        prompt, ``np.ndarray [len(prompts)]``. Pads the call up to the
        smallest (batch, seq) bucket pair; TTFT is this call's wall
        clock (it blocks on the sampled tokens).

        With the prefix cache on, each prompt first consults the
        host-side :class:`PrefixStore`: a hit seeds the slot's KV rows
        from the cached copy and only the SUFFIX picks the seq bucket
        — so a long shared system prompt costs its bucket once,
        ever — and every prefilled prompt's merged rows are cached for
        future hits. ``last_prefill_hits`` records the per-prompt cut
        (0 = miss) for the scheduler's hit accounting."""
        if len(slot_ids) != len(prompts):
            raise ValueError("slot_ids and prompts disagree")
        n = len(prompts)
        plens = [len(p) for p in prompts]
        if min(plens) < 1:
            raise ValueError("empty prompt")
        bbucket = self._pick_bucket(self.config.batch_buckets, n,
                                    "prefill batch")
        ids = self._padded_ids(slot_ids, pad_slot_ids, bbucket)
        if not self._prefix:
            self.last_prefill_hits = [0] * n
            sbucket = self._pick_bucket(self.config.prefill_buckets,
                                        max(plens), "prompt length")
            toks = np.full((bbucket, sbucket),
                           self.config.pad_token_id, np.int32)
            lens = np.ones((bbucket,), np.int32)
            for i, p in enumerate(prompts):
                toks[i, :plens[i]] = np.asarray(p, np.int32)
                lens[i] = plens[i]
            args = self._prefill_args(
                self._put(np.asarray(ids, np.int32)), self._put(toks),
                self._put(lens), None, None, None, self._key())
            out = self._prefill_exec[(bbucket, sbucket)](*args)
            if self._spec_decode:
                self._store, self._draft_store, first = out
            else:
                self._store, first = out
            return np.asarray(first)[:n]
        return self._prefill_seeded(ids, prompts, plens, n, bbucket)

    def _prefill_seeded(self, ids, prompts, plens, n, bbucket):
        """The prefix-cache admission path: look up cuts, assemble the
        per-slot seed stack (cached entry rows on a hit, zeros on a
        miss), prefill only the suffix bucket, then cache the merged
        rows of every newly-seen prompt."""
        lookups = [self.prefix_store.lookup(p, scope=self._scope)
                   for p in prompts]
        cuts = [c for c, _ in lookups]
        suffix_lens = [plen - c for plen, c in zip(plens, cuts)]
        sbucket = self._pick_bucket(self.config.prefill_buckets,
                                    max(suffix_lens),
                                    "prompt suffix length")
        toks = np.full((bbucket, sbucket), self.config.pad_token_id,
                       np.int32)
        lens = np.ones((bbucket,), np.int32)
        starts = np.zeros((bbucket,), np.int32)
        for i, (p, (cut, _)) in enumerate(zip(prompts, lookups)):
            suffix = np.asarray(p, np.int32)[cut:]
            toks[i, :suffix.shape[0]] = suffix
            lens[i] = suffix.shape[0]
            starts[i] = cut
        hits = sum(1 for c in cuts if c)
        if hits:
            # assemble per-slot: entry rows on hit, zeros elsewhere
            prows = self._stack_seed_rows(lookups, bbucket, "rows")
            dprows = self._stack_seed_rows(lookups, bbucket,
                                           "draft_rows") \
                if self._spec_decode else None
        else:
            # miss-only groups reuse the pre-placed zero stack — no
            # host assembly, no fresh transfer
            prows = self._seed_rows_dev(bbucket, "target")
            dprows = self._seed_rows_dev(bbucket, "draft")
        args = self._prefill_args(
            self._put(np.asarray(ids, np.int32)), self._put(toks),
            self._put(lens), self._put(starts), prows, dprows,
            self._key())
        out = list(self._prefill_exec[(bbucket, sbucket)](*args))
        self._store = out.pop(0)
        if self._spec_decode:
            self._draft_store = out.pop(0)
        first = out.pop(0)
        rows = out.pop(0)
        drows = out.pop(0) if self._spec_decode else None
        self.last_prefill_hits = cuts
        self._record_prefix(prompts, plens, cuts, hits, sbucket, rows,
                            drows)
        return np.asarray(first)[:n]

    def _host_zero_row(self, attr):
        key = ("zero_row", attr)
        if key not in self._zero_rows_np:
            spec = self.spec if attr == "rows" else self.draft_spec
            self._zero_rows_np[key] = spec.host_zero_row(tp=self._tp)
        return self._zero_rows_np[key]

    def _stack_seed_rows(self, lookups, bbucket, attr):
        """[bbucket]-stacked host seed rows: cached entry rows where a
        lookup hit, zeros elsewhere (pads included). Entry rows and
        the zero row share the raw model-layout treedef, so one
        tree_map stacks them leaf-wise."""
        zero = self._host_zero_row(attr)
        picks = [getattr(e, attr) if (c and e is not None) else zero
                 for c, e in lookups]
        picks += [zero] * (bbucket - len(picks))
        stacked = jax.tree_util.tree_map(
            lambda *leaves: np.stack(leaves), *picks)
        return self._put_rows(
            stacked, "target" if attr == "rows" else "draft")

    def _record_prefix(self, prompts, plens, cuts, hits, sbucket, rows,
                       drows):
        """Hit accounting + insertion of newly-seen prompts (host
        copies of the RAW merged rows — full precision, so a future
        hit's suffix forward sees exactly what this cold prefill
        saw)."""
        n = len(prompts)
        reg = self._reg()
        if reg.enabled:
            reg.counter("serve/prefix_hits").inc(hits)
            reg.counter("serve/prefix_misses").inc(n - hits)
            hit_toks = sum(cuts)
            if hit_toks:
                reg.counter("serve/prefix_hit_tokens").inc(hit_toks)
            reg.event("serve", "prefix_lookup", prompts=n, hits=hits,
                      hit_tokens=hit_toks, suffix_bucket=sbucket,
                      entries=len(self.prefix_store),
                      store_bytes=self.prefix_store.total_bytes())
        inserts = [i for i in range(n)
                   if plens[i] > self.prefix_store.min_len
                   and not self.prefix_store.covers(prompts[i])]
        if not inserts:
            return
        host_rows = jax.tree_util.tree_map(np.asarray, rows)
        host_drows = jax.tree_util.tree_map(np.asarray, drows) \
            if drows is not None else None
        for i in inserts:
            # np.copy (not ascontiguousarray — that promotes 0-d
            # scalars like cache_index to 1-d) detaches the slice
            row_i = jax.tree_util.tree_map(
                lambda l: np.copy(l[i]), host_rows)
            drow_i = jax.tree_util.tree_map(
                lambda l: np.copy(l[i]), host_drows) \
                if host_drows is not None else None
            self.prefix_store.insert(prompts[i], row_i, drow_i,
                                     scope=self._scope)

    def decode(self, slot_ids, tokens, *, pad_slot_ids=None,
               guarded=True, retries=0, backoff_s=0.05,
               backoff_cap_s=1.0):
        """One decode step for the active ``slot_ids`` fed their last
        ``tokens``; returns ``(next_tokens, finite)`` — each
        ``np.ndarray [len(slot_ids)]``, ``finite[i]`` False iff slot
        ``i``'s logits went non-finite this step (its KV rows are
        already reset in-graph; the scheduler evicts it as
        ``poisoned``). A speculative engine (``spec_enabled``)
        dispatches one fused draft-verify round instead and returns
        ``(emitted [n, k+1], counts [n], finite [n])`` — slot i's
        verified tokens are ``emitted[i, :counts[i]]``; everything
        below (guarding, retries, injection) is identical.

        Dispatch runs under ``resilience.guarded_call``
        (``guarded=False`` opts out): an HBM exhaustion mid-traffic
        writes the memory post-mortem — census labeled with the KV
        cache and weights — and surfaces as ``HBMExhaustedError``.
        ``retries`` re-dispatches after transient failures
        (``robust.is_retryable_decode_error``) with capped exponential
        backoff; past the budget the call raises
        ``robust.DecodeFailedError`` so the caller fails only the
        implicated requests. The injection checkpoint
        (``faults.maybe_fail_decode`` / ``faults.poison_slot_for``)
        is keyed on the engine's lifetime decode-call counter."""
        from apex_tpu import resilience
        from apex_tpu.resilience import faults
        from apex_tpu.serving import robust

        n = len(slot_ids)
        bbucket = self._pick_bucket(self.config.batch_buckets, n,
                                    "decode batch")
        ids = self._padded_ids(slot_ids, pad_slot_ids, bbucket)
        toks = np.zeros((bbucket,), np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        step_idx = self._decode_calls
        self._decode_calls += 1
        poison = faults.poison_slot_for(step_idx)
        key = self._key()
        for attempt in range(int(retries) + 1):
            try:
                faults.maybe_fail_decode(step_idx)
                args = self._decode_args(
                    self._put(np.asarray(ids, np.int32)),
                    self._put(toks), key, self._put(np.int32(poison)))
                if guarded:
                    out = resilience.guarded_call(
                        self._decode_exec[bbucket], *args,
                        registry=self._registry,
                        labels=self.census_labels())
                else:
                    out = self._decode_exec[bbucket](*args)
                break
            except Exception as e:  # noqa: BLE001 — classified below
                if not robust.is_retryable_decode_error(e):
                    raise
                if attempt >= int(retries):
                    raise robust.DecodeFailedError(
                        f"decode call {step_idx} (bucket {bbucket}, "
                        f"slots {list(ids[:n])}) failed "
                        f"{attempt + 1} time(s); retry budget "
                        f"({retries}) exhausted: {e}",
                        attempts=attempt + 1, last_error=e) from e
                self.decode_retries_total += 1
                reg = self._reg()
                reg.counter("serve/decode_retries").inc()
                reg.event("serve", "decode_retry", step=step_idx,
                          attempt=attempt, error=type(e).__name__)
                time.sleep(robust.retry_backoff_s(
                    attempt, backoff_s, backoff_cap_s))
        if self._spec_decode:
            self._store, self._draft_store, emit, counts, finite = out
            return (np.asarray(emit)[:n], np.asarray(counts)[:n],
                    np.asarray(finite)[:n])
        self._store, nxt, finite = out
        return np.asarray(nxt)[:n], np.asarray(finite)[:n]

    def serve(self, requests, *, robust=None, guard=None, **kw):
        """Run a request list to completion through a fresh
        :class:`~apex_tpu.serving.scheduler.Scheduler`; returns
        ``(completed, stats)``. ``robust`` (a
        :class:`~apex_tpu.serving.robust.RobustConfig`) and ``guard``
        (a :class:`~apex_tpu.resilience.preemption.PreemptionGuard`)
        pass through to the scheduler. The convenience entry point
        bench.py's ``serve_decode``/``serve_chaos`` and
        ``chip_smoke.py`` drive."""
        from apex_tpu.serving.scheduler import Scheduler

        sched = Scheduler(self, registry=self._registry, robust=robust,
                          guard=guard)
        completed = sched.run(requests, **kw)
        return completed, sched.stats()
