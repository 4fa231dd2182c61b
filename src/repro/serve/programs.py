"""Program layer of the serving core: the jitted step programs.

:class:`ProgramRegistry` owns every compiled program the executor runs —
decode, whole-prompt prefill + admit graft, chunk streaming, speculative
verify, slot reset, copy-on-write page forks, swap-out/in, position fixup,
and sampling — together with the two mesh concerns the step path should
never touch: routing host arrays through fully-replicated ``device_put``
(:meth:`put`) and pinning program outputs to the profile-resolved
NamedShardings (:meth:`constrain_layers`).

Programs are ``jax.jit`` callables; jit's shape cache keys each one by its
argument shapes, so a program effectively compiles once per (program,
bucket) pair — prompt buckets for prefill/admit, (chunk, page) buckets for
chunk, (k, page) buckets for verify. The Python bodies run only when jit
(re)traces, which is exactly what the per-program ``*_traces`` counters on
the registry count: tests pin them to prove the bucket sets are closed and
mesh-independent.

Nothing here owns scheduling state. The registry reads model config and
sharding context; slots, queues, and pages belong to the executor and
memory layers. Every program takes the weights as an argument: a jitted
function that closed over them would embed them in the program as
constants, gigabytes of them at full width.

Every program that updates the layer state and returns it donates the
state it takes (:func:`donates_state`), except ``verify``, whose input
speculative decoding may replay from: the model's layer scan then writes
the page pool in place, where an undonated pool is copied whole on each
call. The caller rebinds the state to the program's output and never
reads the donated input again. ``pool_inplace_calls`` and
``pool_copy_calls`` count, at dispatch, the calls of those programs on a
paged state that donated it and that did not.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serve.cache import (
    _graft_leaf,
    extract_slot_leaf,
    gather_pages_leaf,
    graft_pages_leaf,
    graft_states,
    insert_slot,
    insert_slot_leaf,
    scatter_pages_leaf,
)
from repro.models import blocks as blk
from repro.serve.step import fresh_slot_layers, init_decode_state
from repro.sharding.rules import ShardingCtx


def paged_cache_bytes(
    cfg, cache_len, n_slots, states, layer_shardings, sctx, mem
) -> dict[str, int]:
    """Actual (peak pages in use) vs contiguous-equivalent cache bytes for
    the paged KV leaves. Zeros when the model has no paged layer."""
    if not mem.paged:
        return {
            "bytes_per_page": 0,
            "peak_bytes": 0,
            "contiguous_bytes": 0,
            "bytes_per_page_per_device": 0,
        }
    # Bytes of one page summed across every paged leaf (a physical page id
    # addresses page-sized storage in every paged layer at once). Sharded,
    # each leaf's per-device share divides by the product of mesh axes its
    # resolved PartitionSpec actually uses — a data-sharded page axis
    # divides too: each device's pool slice holds 1/data of the pages.
    per_page = 0
    per_page_dev = 0
    caps = blk.stack_paged_caps(cfg, cache_len)
    cap_leaves = jax.tree.leaves(caps)
    arr_leaves = jax.tree.leaves(states["layers"])
    sh_leaves = (
        jax.tree.leaves(layer_shardings, is_leaf=lambda x: x is None)
        if layer_shardings is not None
        else [None] * len(arr_leaves)
    )
    mesh_axes = dict(sctx.mesh.shape) if sctx.mesh else {}
    for cap, leafarr, sh in zip(cap_leaves, arr_leaves, sh_leaves):
        if not cap:
            continue
        shape = leafarr.shape
        lead = len(shape) - 4  # stacked layer axis
        n_layers = shape[0] if lead else 1
        page_elems = int(np.prod(shape[lead + 1:]))  # page * kv * hd
        leaf_bytes = n_layers * page_elems * jnp.dtype(leafarr.dtype).itemsize
        per_page += leaf_bytes
        div = 1
        if sh is not None:
            for ax in sh.spec:
                for a in ax if isinstance(ax, tuple) else ((ax,) if ax else ()):
                    div *= mesh_axes.get(a, 1)
        per_page_dev += leaf_bytes // div
    peak = mem.peak_in_use * per_page
    contiguous = n_slots * mem.max_pages * per_page
    return {
        "bytes_per_page": int(per_page),
        "peak_bytes": int(peak),
        "contiguous_bytes": int(contiguous),
        "bytes_per_page_per_device": int(per_page_dev),
    }


def donates_state() -> bool:
    """Whether the programs that update the layer state donate it: on an
    accelerator, yes. On the CPU, where the benchmark's own CPU tests plant
    faults that hand a program's input state back as its output, the
    programs leave their inputs readable."""
    return jax.default_backend() != "cpu"


class _StateProgram:
    """A jitted program over the layer state whose calls on a paged state
    count as in place (the state donated) or as a copy. Attributes other
    than the call (``trace``, ``lower``) are the jitted function's."""

    def __init__(self, registry: "ProgramRegistry", fn, donate_argnums=()):
        self._registry = registry
        self._donated = bool(donate_argnums)
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)

    def __call__(self, *args):
        reg = self._registry
        if reg._paged:
            if self._donated:
                reg.pool_inplace_calls += 1
            else:
                reg.pool_copy_calls += 1
        return self._jit(*args)

    def __getattr__(self, name):
        return getattr(self._jit, name)


def _leaf_page_axis_sharded(arr, sharding) -> bool:
    """True when a pool leaf's physical page axis is mesh-sharded (the
    leading axis, behind the stacked layer axis for 5D leaves)."""
    if sharding is None:
        return False
    spec = sharding.spec
    ax = arr.ndim - 4  # 0 for (P, page, kv, hd), 1 behind a layer axis
    entry = spec[ax] if ax < len(spec) else None
    return bool(entry)


class ProgramRegistry:
    """Compiled programs + trace accounting + sharding glue for one
    scheduler instance. Built once at scheduler construction; the
    executor only ever calls the public program attributes."""

    def __init__(
        self,
        cfg: ModelConfig,
        sctx: ShardingCtx,
        *,
        cache_len: int,
        layouts: Any,
        caps: Any,
        layer_shardings: Any,
        page_size: int = 0,
        paged: bool = False,
    ):
        self.cfg = cfg
        self.sctx = sctx
        self._cache_len = cache_len
        self._layouts = layouts
        self._caps = caps
        self._layer_shardings = layer_shardings
        self._replicated = sctx.replicated()
        self._paged = paged

        self.decode_traces = 0  # jit trace count of the decode hot path
        self.prefill_traces = 0  # one per prompt bucket
        self.admit_traces = 0  # one per prompt bucket
        self.chunk_traces = 0  # one per (chunk, page) bucket
        self.swap_traces = 0  # swap-out + swap-in programs
        self.cow_traces = 0  # copy-on-write fork programs (per fork count)
        self.verify_traces = 0  # one per (k-bucket, page-bucket) pair
        self.pool_inplace_calls = 0  # state programs on a donated pool
        self.pool_copy_calls = 0  # state programs on an undonated pool
        donate = donates_state()

        def program(fn, *state_args):
            return _StateProgram(self, fn, state_args if donate else ())

        def _slot_surgery_trees():
            template = init_decode_state(cfg, 1, cache_len)["layers"]
            c = caps if caps is not None else jax.tree.map(lambda _: 0, template)
            return c, template

        def _freeze_inactive(active, new_layers, old_layers):
            # Inactive slots (free, or PREFILLING between chunks) must keep
            # their per-slot states verbatim across other slots' decode
            # steps: positional KV survives by write-before-read, but a
            # recurrence would absorb the masked slot's garbage token.
            # Shared-pool leaves have no batch row to freeze — their
            # garbage writes stay behind the trash page / the positions the
            # next chunk overwrites.
            c, template = _slot_surgery_trees()

            def leaf(cap, new, old, t):
                if cap:
                    return new
                nd, td = jnp.asarray(new), jnp.asarray(t)
                if nd.shape == td.shape:  # n_slots == 1
                    return jnp.where(active[0], nd, old)
                ax = [i for i in range(nd.ndim) if nd.shape[i] != td.shape[i]][0]
                shape = [1] * nd.ndim
                shape[ax] = nd.shape[ax]
                return jnp.where(active.reshape(shape), nd, old)

            return jax.tree.map(leaf, c, new_layers, old_layers, template)

        def _decode_fn(params, states, token, active):
            # Python body runs only when jit (re)traces: counts compilations.
            self.decode_traces += 1
            logits, new_states = lm.decode_step(params, cfg, states, token, sctx)
            # Freeze inactive slots in place (position and per-slot states).
            new_pos = jnp.where(active, new_states["pos"], states["pos"])
            out = {
                "layers": self.constrain_layers(
                    _freeze_inactive(active, new_states["layers"], states["layers"])
                ),
                "pos": new_pos,
            }
            if "page_table" in new_states:
                out["page_table"] = new_states["page_table"]
            return logits, out

        self.decode = program(_decode_fn, 1)

        def _prefill_fn(p, b):
            self.prefill_traces += 1
            return lm.prefill(p, cfg, b, sctx)

        self.prefill = jax.jit(_prefill_fn)

        if paged:

            def _admit_fn(layers, pos, prefill_layers, slot, page_ids, prompt_len):
                self.admit_traces += 1
                target = init_decode_state(cfg, 1, cache_len)["layers"]

                def leaf(lay, full, tgt, src):
                    if lay.kind == "paged":  # shared-pool KV leaf: scatter pages
                        return graft_pages_leaf(
                            full, src, page_ids, prompt_len, lay.cap, page_size
                        )
                    return insert_slot_leaf(
                        full, _graft_leaf(tgt, src, prompt_len, lay), slot, lay
                    )

                new_layers = self.constrain_layers(
                    jax.tree.map(leaf, layouts, layers, target, prefill_layers)
                )
                return new_layers, pos.at[slot].set(prompt_len)

        else:

            def _admit_fn(layers, pos, prefill_layers, slot, prompt_len):
                self.admit_traces += 1
                target = init_decode_state(cfg, 1, cache_len)
                slot_layers = graft_states(
                    target["layers"], prefill_layers, prompt_len, layouts=layouts
                )
                new_layers = self.constrain_layers(
                    insert_slot(layers, slot_layers, slot, layouts=layouts)
                )
                return new_layers, pos.at[slot].set(prompt_len)

        # slot and prompt_len are traced, so admission compiles once per
        # prefill *shape* — with bucketing, once per bucket.
        self.admit = program(_admit_fn, 0, 1)

        # -- unified-step programs (chunk streaming, slot reset, swap) -------
        def _chunk_body(params, layers, pos, tokens, slot, start, chunk_len,
                        page_ids, all_logits=False):
            c, template = _slot_surgery_trees()
            slot_layers = jax.tree.map(
                lambda lay, cap, full, t: (
                    full if cap else extract_slot_leaf(full, t, slot, lay)
                ),
                layouts, c, layers, template,
            )
            states: dict[str, Any] = {"layers": slot_layers, "pos": start}
            if page_ids is not None:
                states["page_table"] = page_ids[None, :]
            logits, new = lm.chunk_step(
                params, cfg, states, tokens, chunk_len, sctx,
                all_logits=all_logits,
            )
            new_layers = self.constrain_layers(
                jax.tree.map(
                    lambda lay, cap, full, s: (
                        s if cap else insert_slot_leaf(full, s, slot, lay)
                    ),
                    layouts, c, layers, new["layers"],
                )
            )
            return logits, new_layers, pos.at[slot].set(start + chunk_len)

        if paged:

            def _chunk_fn(params, layers, pos, tokens, slot, start, chunk_len, page_ids):
                self.chunk_traces += 1
                return _chunk_body(
                    params, layers, pos, tokens, slot, start, chunk_len, page_ids
                )

            def _verify_fn(params, layers, pos, tokens, slot, start, chunk_len, page_ids):
                self.verify_traces += 1
                return _chunk_body(
                    params, layers, pos, tokens, slot, start, chunk_len, page_ids,
                    all_logits=True,
                )

        else:

            def _chunk_fn(params, layers, pos, tokens, slot, start, chunk_len):
                self.chunk_traces += 1
                return _chunk_body(
                    params, layers, pos, tokens, slot, start, chunk_len, None
                )

            def _verify_fn(params, layers, pos, tokens, slot, start, chunk_len):
                self.verify_traces += 1
                return _chunk_body(
                    params, layers, pos, tokens, slot, start, chunk_len, None,
                    all_logits=True,
                )

        self.chunk = program(_chunk_fn, 1, 2)
        # Verify program for speculative decoding: the chunk body with
        # logits at *every* position, so one call scores a whole draft. Its
        # input state stays readable: a partial accept replays from it.
        self.verify = program(_verify_fn)
        # Position-only fixup for partial acceptance on archs whose caches
        # tolerate garbage past the accepted position (dense / MLA).
        self.setpos = jax.jit(lambda pos, slot, val: pos.at[slot].set(val))

        def _reset_fn(layers, pos, slot, pos_val):
            # Reset the slot's per-slot leaves to the empty-recurrence state
            # so a chunked prefill starts from what a from-scratch prefill
            # would derive. Pool leaves stay: the trash-pointed table row
            # isolates them. ``pos_val`` is the adopted-prefix length (0
            # without sharing): the slot's frozen decode position must sit
            # at the first *unadopted* logical page, or the inactive slot's
            # garbage decode writes would land inside a shared page.
            c, _ = _slot_surgery_trees()
            fresh = fresh_slot_layers(cfg, cache_len)
            new_layers = self.constrain_layers(
                jax.tree.map(
                    lambda lay, cap, full, t: (
                        full if cap else insert_slot_leaf(full, t, slot, lay)
                    ),
                    layouts, c, layers, fresh,
                )
            )
            return new_layers, pos.at[slot].set(pos_val)

        self.reset = program(_reset_fn, 0, 1)

        if paged:

            def _copy_pages(full, src_ids, dst_ids):
                if full.ndim == 5:  # stacked groups: leading layer axis
                    return full.at[:, dst_ids].set(full[:, src_ids])
                return full.at[dst_ids].set(full[src_ids])

            def _cow_fn(layers, src_ids, dst_ids):
                # Fork shared pages: copy page contents src -> dst in every
                # pool leaf (one program per fork count; essentially never
                # runs — the scheduler's write pattern stays past adopted
                # spans — but keeps CoW safety local to the pool). Sharded,
                # the copy runs under shard_map per pool leaf when the page
                # axis is *replicated*: every device owns its
                # kv_heads/head_dim slice of both pages and forks them
                # locally, no cross-device traffic. A page axis sharded
                # over "data" means the global ids index blocks that live
                # on different devices, so those leaves copy under plain
                # jit and let GSPMD lower the gather/scatter (forks stay
                # within one shard's block, so the copy is still local in
                # practice — XLA just has to prove it).
                self.cow_traces += 1
                if self._layer_shardings is None:
                    return jax.tree.map(
                        lambda cap, full: (
                            _copy_pages(full, src_ids, dst_ids) if cap else full
                        ),
                        caps, layers,
                    )

                def leaf(cap, full, sh):
                    if not cap:
                        return full
                    if _leaf_page_axis_sharded(full, sh):
                        return _copy_pages(full, src_ids, dst_ids)
                    spec = sh.spec
                    return jax.shard_map(
                        _copy_pages,
                        mesh=sctx.mesh,
                        in_specs=(spec, P(), P()),
                        out_specs=spec,
                        check_vma=False,
                    )(full, src_ids, dst_ids)

                return jax.tree.map(leaf, caps, layers, self._layer_shardings)

            self.cow = program(_cow_fn, 0)

            def _swap_out_fn(layers, page_ids, slot):
                self.swap_traces += 1
                c, template = _slot_surgery_trees()
                return jax.tree.map(
                    lambda lay, cap, full, t: (
                        gather_pages_leaf(full, page_ids)
                        if cap
                        else extract_slot_leaf(full, t, slot, lay)
                    ),
                    layouts, c, layers, template,
                )

            def _swap_in_fn(layers, pos, snap, page_ids, slot, pos_val):
                self.swap_traces += 1
                c, _ = _slot_surgery_trees()
                new_layers = self.constrain_layers(
                    jax.tree.map(
                        lambda lay, cap, full, s: (
                            scatter_pages_leaf(full, s, page_ids)
                            if cap
                            else insert_slot_leaf(full, s, slot, lay)
                        ),
                        layouts, c, layers, snap,
                    )
                )
                return new_layers, pos.at[slot].set(pos_val)

            self.swap_out = jax.jit(_swap_out_fn)
            self.swap_in = program(_swap_in_fn, 0, 1)

        def _sample_fn(logits, temps, key):
            # Over a vocabulary split across chips, the choice reduces
            # across them.
            with jax.named_scope("head.sample"):
                lg = logits[:, : cfg.vocab_size].astype(jnp.float32)
                greedy = jnp.argmax(lg, axis=-1)
                scaled = lg / jnp.maximum(temps, 1e-6)[:, None]
                sampled = jax.random.categorical(key, scaled, axis=-1)
                return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)

        self.sample = jax.jit(_sample_fn)

    # -- sharding glue --------------------------------------------------------
    def put(self, x):
        """Host array -> device; fully replicated over the mesh when sharded
        so every jit program sees one stable input layout per bucket.

        The transfer gets a private copy: it may read its source after this
        returns (on the CPU the device array may even alias it), while the
        executor keeps mutating its host mirrors — page table, next tokens —
        in place, possibly before the program that reads them has run."""
        x = np.array(x)
        if self._replicated is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._replicated)

    def constrain_layers(self, layers):
        """Pin a step program's output layer tree to the profile-resolved
        NamedShardings (identity without a mesh) — state placement can
        never drift between steps, whatever XLA would have inferred."""
        if self._layer_shardings is None:
            return layers
        return jax.tree.map(
            jax.lax.with_sharding_constraint, layers, self._layer_shardings
        )
