"""Serving engine: continuous batching over a fixed slot grid, with
five-minute-rule-driven KV offload.

The engine owns a decode cache of `max_slots` sequences. Requests are
prefilled into free slots (one jit'd prefill per admission batch) and all
live slots advance together through one jit'd decode step per token
(per-slot fill indices — slots at different positions coexist).

KV tiering (the paper's technique at work): when a request pauses (e.g.
multi-turn sessions) its per-slot KV block is *extracted* and handed to
the TieredStore keyed by session id; the TieringPolicy's observed reuse
interval vs the calibrated break-even threshold decides whether it lands
in host DRAM or flash. On resume the block is re-inserted into a free
slot. This is exactly the paper's "LLM memory layer / session-state"
workload (§VII-A) realized on the serving runtime.

Async KV restore (queueing-aware runtime): `prefetch` issues a session's
KV fetch through `TieredStore.get_async` *before* the slot is needed;
each decode step advances the store's injected clock by `step_time`
(modeled decode compute), so the flash transfer streams behind decode.
`resume` then blocks only on the unfinished remainder — zero stall
whenever the prefetch lead covers the queueing-aware fetch latency.
Stall and miss-under-miss accounting land in the store's `TierStats` /
the runtime's `QueueStats`; `kv_stall_time` totals the decode-visible
stalls. The clock is injectable (deterministic `VirtualClock` default —
see `repro.runtime.clock` for the testing contract).

Multi-host mode (sharded fabric): pass `store=fabric.host_view(host)`
(what `repro.platform.Platform.engine` does) and the engine's store
becomes that host's fabric view — KV blocks shard to their
consistent-hash owner host, and a session paused on one host can resume
on another: `export_session`/`import_session` hand the (tiny) session
metadata between engines while the KV block itself streams cross-host
through the fabric's NIC + remote-flash composition, behind decode when
`prefetch` is issued with enough lead. The old `fabric=`/`host=`
constructor dialect still works as a thin deprecated shim.

Session durability (self-healing fleet): with `checkpoint_interval=N`
every live slot re-puts its KV blob and restart metadata every N decode
steps (and on every pause). When the engine's host dies unplanned
(`fabric.fail_host`), a surviving engine adopts the session from
`checkpoints()` via `restore_checkpoint` — the replicated blob restores
from a surviving holder and greedy decode deterministically regenerates
the at-most-N tokens lost since the last checkpoint. `export_session`
refuses to hand out metadata whose KV blob has no surviving copy (a
torn session is restarted, never resurrected).

Compile behavior (the splice-jit cache): slot splices — admitting a
prefilled prompt into a slot, restoring a resumed session's KV block —
run through module-level jitted functions whose slot index is a
*traced* scalar, so one compiled program serves every slot of every
engine with the same cache geometry (cross-host resumes stop re-jitting
per slot). Prompt lengths are right-padded to power-of-two buckets
(when every cached sublayer is attention — recurrent states would
advance through pad garbage), so prefill compiles once per bucket
instead of once per exact length; causal masking keeps real positions
unaffected and `prefill(last_index=...)` returns the last *real*
token's logits. `splice_trace_counts()` exposes the retrace counters.

Wall-clock spans (`repro.obs.trace.span`, on only while a profiler
trace is wanted) mark each phase of `admit`, `step`, `pause` and
`resume` on the profiler's host plane, so a device trace can put each
idle gap under the host work that caused it. `counters["d2h_bytes"]`
counts, always, the bytes the engine copies from the device to the
host: the step's logits, the first token's logits and the KV blocks
that pauses and checkpoints read.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.policy import Tier, TieringPolicy
from ..models import model as model_lib
from ..models.config import ModelConfig
from ..obs.trace import span
from ..parallel.sharding import Rules
from ..runtime.tiers import TieredStore


# eq=False: the generated dataclass __eq__ would compare the ndarray
# prompts elementwise ("truth value of an array is ambiguous" on any two
# distinct requests) — identity is the only meaningful equality here,
# and schedulers key on `rid` anyway
@dataclasses.dataclass(eq=False)
class Request:
    rid: str
    prompt: np.ndarray            # [S] int32
    max_new: int = 16
    slot: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


# ---------------------------------------------------------------------------
# Splice-jit cache: traced-slot splice programs shared by every engine
# with the same cache geometry. The counters increment only while jax
# traces (a cache miss), so tests can assert reuse across slots, prompt
# buckets and engines.
# ---------------------------------------------------------------------------

_SPLICE_TRACES = {"batch": 0, "block": 0}


def splice_trace_counts() -> Dict[str, int]:
    """Copy of the module-wide splice retrace counters."""
    return dict(_SPLICE_TRACES)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


@jax.jit
def _splice_from_batch(cache, src_cache, slot, src_idx):
    """Write batch element `src_idx` of `src_cache` into `slot` of
    `cache` (both indices traced — one program per cache geometry)."""
    _SPLICE_TRACES["batch"] += 1
    groups = jax.tree.map(
        lambda dst, src: dst.at[:, slot].set(
            jax.lax.dynamic_index_in_dim(src, src_idx, axis=1,
                                         keepdims=False).astype(dst.dtype)),
        cache["groups"], src_cache["groups"])
    tail = jax.tree.map(
        lambda dst, src: dst.at[slot].set(
            jax.lax.dynamic_index_in_dim(src, src_idx, axis=0,
                                         keepdims=False).astype(dst.dtype)),
        cache["tail"], src_cache["tail"])
    return {"groups": groups, "tail": tail}


@jax.jit
def _splice_block(cache, blk, slot):
    """Write an extracted per-slot KV block back into `slot` (traced)."""
    _SPLICE_TRACES["block"] += 1
    groups = jax.tree.map(
        lambda dst, src: dst.at[:, slot].set(src.astype(dst.dtype)),
        cache["groups"], blk["groups"])
    tail = jax.tree.map(
        lambda dst, src: dst.at[slot].set(src.astype(dst.dtype)),
        cache["tail"], blk["tail"])
    return {"groups": groups, "tail": tail}


class DecodeEngine:
    def __init__(self, cfg: ModelConfig, params, rules: Rules, *,
                 max_slots: int = 4, max_len: int = 256,
                 policy: Optional[TieringPolicy] = None,
                 store: Optional[TieredStore] = None,
                 fabric=None, host: int = 0,
                 clock=None, step_time: float = 0.0,
                 checkpoint_interval: int = 0,
                 compute_dtype=jnp.float32, greedy: bool = True):
        self.cfg = cfg
        self.rules = rules
        self.max_slots = max_slots
        self.max_len = max_len
        self.dtype = compute_dtype
        self.greedy = greedy
        # params and caches are committed to the rules' mesh: every
        # engine's programs see committed arguments on its own device,
        # so engines on one device share compiled programs and an engine
        # on another device never computes on (or hops through) device 0
        placement = NamedSharding(rules.mesh, P())
        self.params = jax.device_put(params, placement)

        # named functions, not partials: a device trace shows each
        # program under its name (`init_cache`, `prefill`, `decode_step`)
        def init_cache(batch):
            return model_lib.init_cache(cfg, batch, max_len=max_len,
                                        dtype=compute_dtype)

        self._zero_cache = jax.jit(init_cache, static_argnums=0,
                                   out_shardings=placement)
        self.cache = self._zero_cache(max_slots)
        self.lengths = np.zeros(max_slots, np.int32)    # filled positions
        self.live = np.zeros(max_slots, bool)
        # parked slots: live (KV resident, slot held) but not decoding —
        # a scheduler keeps short-gap multi-turn sessions resident
        # instead of paying the offload/restore round trip
        self.active = np.zeros(max_slots, bool)
        self.last_token = np.zeros(max_slots, np.int32)  # decode inputs
        self.slot_req: Dict[int, Request] = {}
        self.policy = policy or TieringPolicy(tau_hot=0.05, tau_be=5.0)
        if store is None and fabric is not None:
            # legacy constructor dialect — the declarative path is
            # Platform.engine(...) / store=fabric.host_view(host)
            warnings.warn(
                "DecodeEngine(fabric=..., host=...) is deprecated; "
                "compile a repro.platform.HierarchySpec and use "
                "Platform.engine(..., host=...), or pass "
                "store=fabric.host_view(host)", DeprecationWarning,
                stacklevel=2)
            store = fabric.host_view(host)
        elif store is not None:
            # a fabric host view carries its own host identity
            host = getattr(store, "host", host)
        self.host = host
        self.store = store or TieredStore(self.policy, clock=clock)
        self.clock = self.store.clock
        self.step_time = step_time      # modeled seconds of decode compute
        self.kv_stall_time = 0.0        # decode-visible restore stalls
        # observability rides in on the store (single-host or fabric
        # view): session lifecycle instants + causal flows join the
        # transfer spans the runtime already records
        self.obs = getattr(self.store, "obs", None)
        self._paused: Dict[str, tuple] = {}
        self._pending: Dict[str, object] = {}   # rid -> PendingFetch
        # periodic session durability: every `checkpoint_interval` decode
        # steps (0 = off) live slots re-put their KV blob and refresh the
        # restart metadata below, so an unplanned host failure loses at
        # most the tokens generated since the last checkpoint
        self.checkpoint_interval = int(checkpoint_interval)
        self._checkpoints: Dict[str, tuple] = {}
        self.steps = 0
        # prompt-length bucketing is sound only when no cached sublayer
        # carries recurrent state (pads would advance it) and there is
        # no encoder prefix
        self._bucket_prompts = cfg.encoder is None and all(
            spec.kind in ("attn", "ffn", "moe")
            for *_ignored, spec in cfg.sublayers())
        self.jit_stats = {"prefill_traces": 0}
        self.counters = {"d2h_bytes": 0}

        def prefill(params, batch, cache, last_index=None):
            self.jit_stats["prefill_traces"] += 1
            return model_lib.prefill(params, cfg, rules, batch, cache,
                                     compute_dtype=compute_dtype,
                                     last_index=last_index)

        def decode_step(params, token, cache, index):
            return model_lib.decode_step(params, cfg, rules, token, cache,
                                         index, compute_dtype=compute_dtype)

        self._prefill = jax.jit(prefill)
        # the step consumes the cache it is given: written in place, the
        # new K/V rows are all it moves (`step` rebinds `self.cache`)
        self._decode = jax.jit(decode_step, donate_argnames="cache")

    # -------------------------------------------------------- observability
    def _trace_session(self, name: str, rid: str, flow: str = "",
                       **args) -> None:
        """Session-lifecycle instant on this engine's track; `flow`
        ("s"/"t"/"f") stitches the event into the session's causal
        chain (admission -> prefetch -> fetch spans -> resume)."""
        if self.obs is None or self.obs.tracer is None:
            return
        t = self.obs.tracer
        track = t.track(f"host{self.host}", "engine")
        now = self.clock.now()
        t.instant(track, name, now, cat="session",
                  args={"rid": rid, **args})
        if flow == "s":
            t.flow_start(track, f"session:{rid}", now, ("session", rid))
        elif flow == "t":
            t.flow_step(track, f"session:{rid}", now, ("session", rid))
        elif flow == "f":
            t.flow_end(track, f"session:{rid}", now, ("session", rid))

    # ------------------------------------------------------------ admission
    def _free_slots(self) -> List[int]:
        return [i for i in range(self.max_slots) if not self.live[i]]

    def admit(self, req: Request):
        """Prefill a request into a free slot (single-sequence prefill
        batched into the slot grid via masking writes). Prompts are
        right-padded to a power-of-two bucket when sound (attention-only
        caches): prefill compiles once per bucket, the causal mask keeps
        real positions pad-independent, decode masks positions beyond
        the fill index, and `last_index` picks the real last logits."""
        with span("engine.admit", rid=req.rid):
            free = self._free_slots()
            if not free:
                raise RuntimeError("no free slots")
            slot = free[0]
            S = len(req.prompt)
            assert S < self.max_len
            tokens = req.prompt
            if self._bucket_prompts:
                L = min(_next_pow2(S), self.max_len - 1)
                if L > S:
                    tokens = np.concatenate(
                        [req.prompt, np.zeros(L - S, req.prompt.dtype)])
            # a batch-1 prefill against a temp cache, then splice the slot
            with span("engine.prefill"):
                tmp_cache = self._zero_cache(1)
                batch = {"tokens": jnp.asarray(tokens[None, :])}
                if self.cfg.encoder is not None:
                    batch["frames"] = jnp.zeros(
                        (1, self.cfg.encoder.n_frames, self.cfg.d_model),
                        self.dtype)
                if self._bucket_prompts:
                    tmp_cache, logits = self._prefill(
                        self.params, batch=batch, cache=tmp_cache,
                        last_index=jnp.asarray(S - 1, jnp.int32))
                else:
                    tmp_cache, logits = self._prefill(
                        self.params, batch=batch, cache=tmp_cache)
            with span("engine.splice"):
                self._splice_slot(tmp_cache, slot)
            self.lengths[slot] = S
            self.live[slot] = True
            self.active[slot] = True
            req.slot = slot
            self.slot_req[slot] = req
            first = 0
            if self.greedy:
                with span("engine.first_token"):
                    row = np.asarray(logits[0])
                    first = int(np.argmax(row))
                self.counters["d2h_bytes"] += row.nbytes
            req.generated.append(first)
            self.last_token[slot] = first
            self._trace_session("admit", req.rid, flow="s", slot=slot,
                                prompt_len=S)
            return slot

    def _splice_slot(self, src_cache, slot: int, src_idx: int = 0):
        # group caches are stacked [G, B, ...] (batch at dim 1); tail
        # caches are unstacked [B, ...] (batch at dim 0). Both indices
        # are traced, so one compiled program serves every slot.
        self.cache = _splice_from_batch(
            self.cache, src_cache, jnp.asarray(slot, jnp.int32),
            jnp.asarray(src_idx, jnp.int32))

    def _extract_slot(self, slot: int):
        blk = {
            "groups": jax.tree.map(lambda a: np.asarray(a[:, slot]),
                                   self.cache["groups"]),
            "tail": jax.tree.map(lambda a: np.asarray(a[slot]),
                                 self.cache["tail"]),
        }
        self.counters["d2h_bytes"] += sum(
            a.nbytes for a in jax.tree.leaves(blk))
        return blk

    def _slot_of_rid(self, rid: str) -> int:
        """Slot currently decoding `rid`; KeyError (not a bare
        StopIteration out of `next`) when the session is not live here —
        unknown, already paused, or finished."""
        for s, r in self.slot_req.items():
            if r.rid == rid:
                return s
        state = ("paused" if rid in self._paused else "not live")
        raise KeyError(f"session {rid!r} is {state} on this engine; "
                       f"only live sessions can be paused or "
                       f"checkpointed")

    # -------------------------------------------------------------- pausing
    def pause(self, rid: str):
        """Offload a session's KV block through the tiered store."""
        with span("engine.pause", rid=rid):
            slot = self._slot_of_rid(rid)
            req = self.slot_req.pop(slot)
            with span("engine.extract"):
                blk = self._extract_slot(slot)
                flat = jax.tree.leaves(blk)
                blob = np.concatenate([np.asarray(l, np.float32).ravel()
                                       for l in flat])
            with span("engine.put"):
                self.store.put(("kv", rid), blob)
            state = (req, jax.tree.structure(blk),
                     [(l.shape, l.dtype) for l in flat],
                     int(self.lengths[slot]))
            self._paused[rid] = state
            # a pause is also the freshest durable point for the session
            self._checkpoints[rid] = state
            self.live[slot] = False
            self.active[slot] = False
            self.lengths[slot] = 0
            tier = self.store.tier_of(("kv", rid))
            self._trace_session("pause", rid, flow="t", slot=slot,
                                tier=getattr(tier, "name", str(tier)))
            return tier

    def park(self, rid: str) -> int:
        """Idle a live session in place: the slot and its KV stay
        resident but the slot stops decoding (no token append, no
        length advance) until `unpark`. Cheaper than `pause`/`resume`
        for short inter-turn gaps — no offload, no restore stall."""
        slot = self._slot_of_rid(rid)
        self.active[slot] = False
        return slot

    def unpark(self, rid: str) -> int:
        """Reactivate a parked session; decode picks up exactly where
        it left off (the parked slot's pending KV position is rewritten
        by the first real decode)."""
        slot = self._slot_of_rid(rid)
        self.active[slot] = True
        return slot

    # -------------------------------------------------------- checkpointing
    def checkpoint_session(self, rid: str):
        """Durable snapshot of a *live* session without evicting it: the
        slot's KV block is re-put to the store under the usual
        (\"kv\", rid) key (replicated when the store is a fabric view
        with replicas >= 2) and restart metadata is recorded, but decode
        keeps running in place. After an unplanned failure of this host,
        a surviving engine `import_session`s the checkpoint and `resume`s
        from the checkpointed position — greedy decode regenerates the
        lost tail deterministically."""
        slot = self._slot_of_rid(rid)
        req = self.slot_req[slot]
        blk = self._extract_slot(slot)
        flat = jax.tree.leaves(blk)
        blob = np.concatenate([np.asarray(l, np.float32).ravel()
                               for l in flat])
        self.store.put(("kv", rid), blob)
        # snapshot the request: later decode steps on this engine must
        # not mutate the checkpointed token list
        self._checkpoints[rid] = (
            dataclasses.replace(req, slot=None,
                                generated=list(req.generated)),
            jax.tree.structure(blk), [(l.shape, l.dtype) for l in flat],
            int(self.lengths[slot]))
        return self.store.tier_of(("kv", rid))

    def checkpoint_live(self):
        """Checkpoint every live, unfinished session (slot order)."""
        rids = [r.rid for s, r in sorted(self.slot_req.items())
                if self.live[s] and not r.done]
        for rid in rids:
            self.checkpoint_session(rid)
        return rids

    def checkpoints(self) -> Dict[str, tuple]:
        """rid -> restart state, same tuple format `import_session`
        takes. What a failover controller reads off a dead engine's
        last known state (the metadata is tiny and assumed mirrored;
        the KV blob's durability is the fabric's replication)."""
        return dict(self._checkpoints)

    def restore_checkpoint(self, rid: str, state=None):
        """Re-admit a session from its last checkpoint (here or, with
        `state` from another engine's `checkpoints()`, after failover).
        Returns the landing slot; the session re-decodes from the
        checkpointed position."""
        if state is None:
            state = self._checkpoints[rid]
        if rid not in self._paused:
            self.import_session(rid, state)
        return self.resume(rid)

    def export_session(self, rid: str):
        """Hand a paused session off to another host's engine: returns
        the session metadata (request + KV tree spec — a few hundred
        bytes). The KV block itself stays in the tiered store/fabric and
        streams to the resuming host on its `prefetch`/`resume`."""
        # an issued prefetch belongs to this host's vantage point; just
        # drop the handle — the in-flight transfer completes on its own
        # in the background, and waiting here would advance the shared
        # clock for data nobody will consume
        self._pending.pop(rid, None)
        state = self._paused.pop(rid)
        # torn-session guard: metadata must never outlive the KV blob.
        # `tier_of` is a structural check — a mid-flight ingest (readability
        # -gated restore, repair stream) already has its placement recorded
        # and any read pays the arrival gate, so exporting it is safe; only
        # a blob with *no* surviving copy anywhere makes the metadata
        # unresumable, and handing it out would resurrect a torn session
        # on some other host.
        if self.store.tier_of(("kv", rid)) is None:
            self._paused[rid] = state
            raise KeyError(
                f"session {rid!r}: KV blob has no surviving copy; "
                f"cannot export a torn session")
        self._checkpoints.pop(rid, None)
        return state

    def import_session(self, rid: str, state):
        """Adopt a session exported by another engine on the same store
        or fabric; `prefetch`/`resume` then work as if paused here."""
        if rid in self._paused:
            raise KeyError(f"session {rid!r} already paused here")
        self._paused[rid] = state

    def locality_host(self, rid: str) -> int:
        """Host a resuming session should be routed to: one already
        holding its KV replica (the remote NIC + remote-flash restore
        becomes a plain local read), else this engine's host. Only
        meaningful in fabric mode — a single-host store is its own
        locality."""
        fab = getattr(self.store, "fabric", None)
        if fab is None:
            return self.host
        return fab.preferred_host(("kv", rid), default=self.host)

    def prefetch_lead(self, rid: str) -> int:
        """p99-sized prefetch lead for `rid` in decode steps: how many
        steps before the slot is needed `prefetch` should be called so
        the tail-aware fetch estimate (owner flash p99 + NIC leg when
        remote) is covered by modeled decode compute. Falls back to one
        step when the store predates lead sizing or `step_time` is 0."""
        lead_fn = getattr(self.store, "prefetch_lead_steps", None)
        if lead_fn is None or self.step_time <= 0:
            return 1
        return lead_fn(("kv", rid), self.step_time)

    def prefetch(self, rid: str):
        """Issue a paused session's KV restore asynchronously: the fetch
        streams from its tier while decode steps keep advancing the clock.
        Idempotent; returns the pending handle."""
        if rid not in self._paused:
            raise KeyError(rid)
        if rid not in self._pending:
            self._pending[rid] = self.store.get_async(("kv", rid))
            self._trace_session("prefetch", rid, flow="t")
        return self._pending[rid]

    def prefetch_many(self, rids):
        """Batched async restore: issue all fetches back-to-back so the
        flash queue pipelines them (miss-under-miss)."""
        return [self.prefetch(r) for r in rids]

    def resume(self, rid: str):
        """Re-admit a paused session. Blocks only on the unfinished part
        of its (pre)fetch; the stall lands in `kv_stall_time`."""
        if rid not in self._paused:
            raise KeyError(f"session {rid!r} is not paused on this "
                           f"engine")
        with span("engine.resume", rid=rid):
            # secure the slot *before* consuming any session state: the
            # no-free-slots failure must leave the session fully
            # resumable (metadata in `_paused`, any issued prefetch
            # still pending)
            free = self._free_slots()
            if not free:
                raise RuntimeError("no free slots")
            slot = free[0]
            req, treedef, shapes, length = self._paused.pop(rid)
            pf = self._pending.pop(rid, None)
            if pf is None:
                pf = self.store.get_async(("kv", rid))
            t0 = self.clock.now()
            with span("engine.wait"):
                blob = pf.wait()
            stall = self.clock.now() - t0
            self.kv_stall_time += stall
            self._trace_session("resume", rid, flow="f", slot=slot,
                                stall=stall)
            with span("engine.restore"):
                leaves, off = [], 0
                for shape, dtype in shapes:
                    n = int(np.prod(shape))
                    leaves.append(np.asarray(
                        blob[off:off + n].reshape(shape), dtype))
                    off += n
                blk = jax.tree.unflatten(treedef, leaves)
                # traced-slot splice: repeated (cross-host) resumes
                # reuse one compiled program regardless of the slot
                self.cache = _splice_block(self.cache, blk,
                                           jnp.asarray(slot, jnp.int32))
            self.lengths[slot] = length
            self.live[slot] = True
            self.active[slot] = True
            if req.generated:
                self.last_token[slot] = req.generated[-1]
            req.slot = slot
            self.slot_req[slot] = req
            return slot

    # ---------------------------------------------------------------- step
    def step(self):
        """One decode step for all live, non-parked slots (vectorized
        across the slot grid: token gather, argmax and length advance
        are whole-array ops; Python only touches slots that finish this
        step). Parked and dead slots ride through the fixed-shape decode
        but their state is masked out — the garbage KV written at their
        pending position is overwritten by the first real decode after
        unpark/admit."""
        act = self.live & self.active
        if not act.any():
            return
        with span("engine.step", step=self.steps):
            with span("engine.launch"):
                idx = jnp.asarray(self.lengths)
                self.cache, logits = self._decode(
                    self.params, token=jnp.asarray(self.last_token[:, None]),
                    cache=self.cache, index=idx)
            self.steps += 1
            if self.step_time:
                # modeled decode compute overlaps in-flight KV transfers
                self.store.runtime.advance(self.step_time)
            with span("engine.fetch"):
                host = np.asarray(logits)
            self.counters["d2h_bytes"] += host.nbytes
            with span("engine.sample"):
                nxt = np.argmax(host, axis=-1).astype(np.int32)
                self.last_token = np.where(act, nxt, self.last_token)
                self.lengths[act] += 1
            with span("engine.retire"):
                for slot, req in list(self.slot_req.items()):
                    if not act[slot]:
                        continue
                    req.generated.append(int(nxt[slot]))
                    if (len(req.generated) >= req.max_new
                            or self.lengths[slot] >= self.max_len - 1):
                        req.done = True
                        self.live[slot] = False
                        self.active[slot] = False
                        del self.slot_req[slot]
                        self._checkpoints.pop(req.rid, None)
                if (self.checkpoint_interval and self.live.any()
                        and self.steps % self.checkpoint_interval == 0):
                    self.checkpoint_live()

    def run(self, requests: List[Request], max_steps: int = 1000):
        """Simple gang scheduler loop: admit as slots free up, decode
        until all requests complete. Completion is tracked by rid (the
        old `r not in done` identity scan was O(n^2) per step)."""
        pending = list(requests)
        done: List[Request] = []
        done_rids = set()
        steps = 0
        while (pending or self.live.any()) and steps < max_steps:
            while pending and self._free_slots():
                self.admit(pending.pop(0))
            self.step()
            steps += 1
            for r in requests:
                if r.done and r.rid not in done_rids:
                    done_rids.add(r.rid)
                    done.append(r)
        return done


def route_session(engines: Dict[int, "DecodeEngine"], rid: str,
                  state=None) -> "DecodeEngine":
    """Locality-aware session routing across a fleet of engines (one per
    fabric host): pick the engine whose host already holds the session's
    KV replica, so the restore is a local flash read instead of the NIC
    + remote-flash composition. Falls back to the first engine when no
    replica exists (fresh session) or the holder runs no engine. When
    `state` (from `export_session`) is given, the session is imported
    into the chosen engine."""
    if not engines:
        raise ValueError("no engines to route over")
    first = next(iter(engines.values()))
    host = first.locality_host(rid)
    target = engines.get(host, first)
    if state is not None:
        target.import_session(rid, state)
    return target
