"""Slot-based continuous-batching decode engine over a KV-cache arena.

The fixed-batch server (``launch.serve.BatchedServer``) couples every
request's latency to its batch-mates: a request that lands just after a
batch fires waits a full batch-fill interval, and off-peak traffic strands
sub-batch residuals.  Continuous batching decouples them (ROADMAP item 2):

* ``init_arena`` allocates a fixed-capacity KV-cache *arena* — per layer
  ``(slots, kv, max_len, head_dim)``, head-major so the decode kernel's K/V
  tiles are contiguous — plus one per-slot ``lengths`` counter.  A slot IS a
  request's cache residency for its whole lifetime.
* ``arena_prefill`` runs the full-sequence forward for newly admitted
  prompts and scatters their K/V rows into freed slots.  The call is padded
  to a single static shape; out-of-bounds slot ids mark padding rows whose
  writes drop (``kernels.decode_attention.ops`` slot paths).
* ``arena_decode`` advances every active slot one token in ONE fused jitted
  dispatch: per-slot RoPE positions, per-slot ragged cache writes, and
  ragged-``lengths`` attention via ``kernels.decode_attention``.  Slots at
  different sequence positions decode together — that is the whole trick.
* ``ContinuousBatchingEngine`` is the host-side slot manager: finished
  requests retire their slot at the iteration end, queued requests prefill
  into freed slots at the next iteration boundary.  Scheduling never needs
  token *values* (greedy decode to a fixed budget), so the decode loop runs
  sync-free: token arrays are stacked and fetched once, at report time.
* ``ContinuousServer`` adapts the engine to DeviceFlow's delivery callback
  on the shared ``VirtualClock``.  Service time comes from a deterministic
  ``ServeCostModel`` charged identically to both serving modes, so latency
  comparisons measure *scheduling*, not host wall-clock noise.

Stale-KV safety: a reused slot's rows beyond the new prompt keep the retired
request's K/V, but the slot's length counter is reset at prefill and only
ever covers rows the current occupant wrote — attention masks the rest
(tested against a zero-filled cache in ``tests/test_kernels.py``).

Models that keep their own per-layer state (recurrent state beside K/V,
as the ``granite_hybrid`` family does) bring slot steps through their
``ModelApi`` (``init_state``, ``prefill_slots``, ``decode_slots``), and the
three arena functions dispatch to them.  A length counter cannot mask stale
recurrent state, so such a prefill overwrites all of a slot's state; with
``ModelApi.donate_state`` the engine donates the state to each step, which
updates it in place.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import heapq
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.analysis.sanitizers import hot_path
from repro.configs.base import ModelConfig
from repro.distribution import ctx as shard_ctx
from repro.kernels.decode_attention.ops import (
    decode_attention,
    scatter_decode_token,
    scatter_prefill_rows,
    tuned_block_k,
)
from repro.models import moe as moe_lib
from repro.models.layers import (
    _attend,
    _project_qkv,
    embed_apply,
    mlp_apply,
    rmsnorm,
    rope,
    unembed_apply,
)
from repro.models.registry import get_model

__all__ = [
    "ServeCostModel",
    "RequestRecord",
    "IterationStats",
    "ServingReport",
    "ContinuousBatchingEngine",
    "ContinuousServer",
    "init_params",
    "init_arena",
    "arena_prefill",
    "arena_decode",
]


# --------------------------------------------------------------------------- #
# Virtual-time cost model + request accounting
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ServeCostModel:
    """Deterministic virtual-time cost of one serving dispatch.

    A prefill over ``m`` prompts costs ``prefill_base_s + m *
    prefill_per_req_s``; one decode iteration over ``n`` active sequences
    costs ``decode_base_s + n * decode_per_slot_s``.  Charged from the same
    model to the fixed-batch and continuous servers, so their virtual-time
    latency difference is purely the batching policy.
    """

    prefill_base_s: float = 4e-3
    prefill_per_req_s: float = 1e-3
    decode_base_s: float = 1.5e-3
    decode_per_slot_s: float = 2.5e-4

    def prefill_s(self, n_requests: int) -> float:
        if n_requests <= 0:
            return 0.0
        return self.prefill_base_s + n_requests * self.prefill_per_req_s

    def decode_s(self, n_active: int) -> float:
        if n_active <= 0:
            return 0.0
        return self.decode_base_s + n_active * self.decode_per_slot_s


@dataclasses.dataclass
class RequestRecord:
    """One request's serving timeline + greedy-decoded tokens."""

    request_id: int
    arrival_t: float
    prompt: np.ndarray | None = None
    start_t: float | None = None  # admission (prefill begins)
    first_token_t: float | None = None  # prefill completes → first token
    finish_t: float | None = None
    slot: int | None = None
    decoded: int = 0  # decode-step tokens produced (excludes prefill token)
    tokens: list[int] = dataclasses.field(default_factory=list)

    @property
    def latency_s(self) -> float | None:
        return None if self.finish_t is None else self.finish_t - self.arrival_t

    @property
    def ttft_s(self) -> float | None:
        return (None if self.first_token_t is None
                else self.first_token_t - self.arrival_t)


@dataclasses.dataclass(frozen=True)
class IterationStats:
    """One engine iteration: when it ran, what it admitted/decoded."""

    t: float
    duration_s: float
    admitted: int
    n_active: int  # slots decoding this iteration (occupancy)
    queue_depth: int  # requests still waiting after admission


@dataclasses.dataclass
class ServingReport:
    """Latency/goodput rollup over a set of ``RequestRecord``s."""

    records: list[RequestRecord]
    horizon_s: float  # virtual span the run covered (goodput denominator)

    def finished(self) -> list[RequestRecord]:
        return [r for r in self.records if r.finish_t is not None]

    def _pct(self, values: list[float], q: float) -> float:
        return float(np.percentile(np.asarray(values), q)) if values else 0.0

    @property
    def p50_latency_s(self) -> float:
        return self._pct([r.latency_s for r in self.finished()], 50.0)

    @property
    def p99_latency_s(self) -> float:
        return self._pct([r.latency_s for r in self.finished()], 99.0)

    @property
    def p50_ttft_s(self) -> float:
        return self._pct([r.ttft_s for r in self.records
                          if r.first_token_t is not None], 50.0)

    @property
    def p99_ttft_s(self) -> float:
        return self._pct([r.ttft_s for r in self.records
                          if r.first_token_t is not None], 99.0)

    def goodput_rps(self, slo_s: float) -> float:
        """Finished requests meeting the latency SLO, per virtual second."""
        ok = sum(1 for r in self.finished() if r.latency_s <= slo_s)
        return ok / self.horizon_s if self.horizon_s > 0 else 0.0

    def summary(self, slo_s: float) -> dict:
        fin = self.finished()
        return {
            "requests": len(self.records),
            "finished": len(fin),
            "p50_latency_s": self.p50_latency_s,
            "p99_latency_s": self.p99_latency_s,
            "p50_ttft_s": self.p50_ttft_s,
            "p99_ttft_s": self.p99_ttft_s,
            "goodput_rps": self.goodput_rps(slo_s),
            "slo_s": slo_s,
            "slo_attainment": (sum(1 for r in fin if r.latency_s <= slo_s)
                               / len(fin)) if fin else 0.0,
            "horizon_s": self.horizon_s,
        }


# --------------------------------------------------------------------------- #
# Weights, KV arena + fused jitted arena ops
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnums=(0, 1))
def init_params(cfg: ModelConfig, seed: int) -> Any:
    """A model's random weights from ``seed``, built in one jit so only the
    final (bf16) arrays materialize: an eager init of a published-width MoE
    would also hold each expert stack's f32 RNG temporaries on the device."""
    return get_model(cfg).init(jax.random.PRNGKey(seed), cfg)


def init_arena(cfg: ModelConfig, slots: int, max_len: int) -> dict:
    """Fixed-capacity KV arena: per-layer head-major ``(slots, kv, max_len,
    hd)`` caches plus one per-slot ``lengths`` counter (0 = empty/retired
    slot).  A model with slot steps keeps its own per-layer state under
    ``layers`` instead of ``kv``."""
    lengths = jnp.zeros((slots,), jnp.int32)
    init_state = get_model(cfg).init_state
    if init_state is not None:
        return {"layers": init_state(cfg, slots, max_len), "lengths": lengths}
    dt = jnp.dtype(cfg.dtype)

    def one():
        shape = (slots, cfg.num_kv_heads, max_len, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    if cfg.scan_layers:
        kv = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one())
    else:
        kv = [one() for _ in range(cfg.num_layers)]
    return {"kv": kv, "lengths": lengths}


def _mlp_or_moe(lp, hn, cfg):
    if cfg.num_experts:
        impl = shard_ctx.moe_impl() or moe_lib.moe_apply
        m, _ = impl(lp["moe"], hn, cfg)
        return m
    return mlp_apply(lp["mlp"], hn, cfg)


def _run_layers(params, x, cfg, run_layer, kv):
    """Drive ``run_layer(lp, h, kc, vc) -> (h, kc, vc)`` across the stack in
    the params' layout (``lax.scan`` over stacked layers, or a Python loop),
    threading each layer's arena K/V through and re-stacking the updates."""
    if cfg.scan_layers:
        def body(h, xs):
            lp, layer_kv = xs
            h, kc, vc = run_layer(lp, h, layer_kv["k"], layer_kv["v"])
            return h, {"k": kc, "v": vc}
        x, kv = jax.lax.scan(body, x, (params["layers"], kv))
    else:
        kv = list(kv)
        for i, (lp, layer_kv) in enumerate(zip(params["layers"], kv)):
            x, kc, vc = run_layer(lp, x, layer_kv["k"], layer_kv["v"])
            kv[i] = {"k": kc, "v": vc}
    return x, kv


def arena_prefill(params, tokens: jax.Array, slot_ids: jax.Array,
                  arena: dict, cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """Prefill admitted prompts into their arena slots.

    ``tokens`` is ``(m, s) int32`` and ``slot_ids`` ``(m,) int32``; rows with
    ``slot_ids[i] >= slots`` are padding (computed then dropped), so the jit
    sees ONE static shape however many requests joined this iteration.
    Returns ``(first greedy token (m,) int32, arena')`` — the prefill's
    last-position logits already yield each request's first token.
    """
    prefill_slots = get_model(cfg).prefill_slots
    if prefill_slots is not None:
        logits, layers = prefill_slots(params, tokens, slot_ids,
                                       arena["layers"], cfg)
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        lengths = arena["lengths"].at[slot_ids].set(tokens.shape[1],
                                                    mode="drop")
        return tok, {"layers": layers, "lengths": lengths}
    x = embed_apply(params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def run_layer(lp, h, kc, vc):
        hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        o = _attend(q, k, v, cfg, causal=True)
        h = h + o.reshape(b, s, -1) @ lp["attn"]["wo"]
        hn = rmsnorm(h, lp["ln2"], cfg.norm_eps)
        kc = scatter_prefill_rows(kc, k.astype(kc.dtype), slot_ids)
        vc = scatter_prefill_rows(vc, v.astype(vc.dtype), slot_ids)
        return h + _mlp_or_moe(lp, hn, cfg), kc, vc

    x, kv = _run_layers(params, x, cfg, run_layer, arena["kv"])
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed_apply(params["embed"], x[:, -1])
    tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
    lengths = arena["lengths"].at[slot_ids].set(s, mode="drop")
    return tok, {"kv": kv, "lengths": lengths}


def arena_decode(params, tok: jax.Array, active: jax.Array, arena: dict,
                 cfg: ModelConfig, *, attn_impl: str = "auto",
                 block_k: int | None = None) -> tuple[jax.Array, dict]:
    """One fused decode iteration across every arena slot.

    ``tok`` is ``(slots,) int32`` — each slot's last token; ``active`` is
    ``(slots,) bool``.  Active slots write K/V at their own cache position
    and attend over their own ragged length; inactive slots neither write
    nor advance (their held token is passed through).  Per-row math is
    identical to the fixed-batch ``layers.attention_decode`` path, which is
    what makes continuous batching token-identical to the fixed reference.
    """
    slots = tok.shape[0]
    lengths = arena["lengths"]
    decode_slots = get_model(cfg).decode_slots
    if decode_slots is not None:
        logits, layers = decode_slots(
            params, tok, active, lengths, arena["layers"], cfg,
            attn_impl=attn_impl, block_k=block_k)
        nxt = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        return (jnp.where(active, nxt, tok),
                {"layers": layers,
                 "lengths": lengths + active.astype(jnp.int32)})
    kv = arena["kv"]
    max_len = (kv["k"].shape[3] if cfg.scan_layers else kv[0]["k"].shape[2])
    if block_k is None:
        block_k = tuned_block_k(max_len, head_dim=cfg.head_dim)
    x = embed_apply(params["embed"], tok[:, None])  # (slots, 1, d)
    pos2d = lengths[:, None]  # per-slot RoPE position for the new token
    write_pos = jnp.where(active, lengths, max_len)  # OOB → write drops
    lens_att = lengths + active.astype(jnp.int32)

    def run_layer(lp, h, kc, vc):
        hn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg)  # (slots, 1, heads, hd)
        q = rope(q, pos2d, cfg.rope_theta)
        k = rope(k, pos2d, cfg.rope_theta)
        kc = scatter_decode_token(kc, k[:, 0].astype(kc.dtype), write_pos)
        vc = scatter_decode_token(vc, v[:, 0].astype(vc.dtype), write_pos)
        o = decode_attention(q[:, 0], kc, vc, lens_att,
                             impl=attn_impl, block_k=block_k)
        h = h + o.reshape(slots, 1, -1) @ lp["attn"]["wo"]
        hn = rmsnorm(h, lp["ln2"], cfg.norm_eps)
        return h + _mlp_or_moe(lp, hn, cfg), kc, vc

    x, kv = _run_layers(params, x, cfg, run_layer, kv)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed_apply(params["embed"], x[:, 0])
    nxt = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, tok)
    return nxt, {"kv": kv, "lengths": lengths + active.astype(jnp.int32)}


def _state_and_lengths(arena: dict) -> tuple[dict, jax.Array]:
    return ({k: v for k, v in arena.items() if k != "lengths"},
            arena["lengths"])


# --------------------------------------------------------------------------- #
# Engine: host-side slot manager
# --------------------------------------------------------------------------- #
class ContinuousBatchingEngine:
    """Iteration-at-a-time continuous batching over the KV arena.

    Each ``step(t)``: (1) admit queued requests into free slots and prefill
    them (one padded jitted call), (2) run one fused ``arena_decode`` over
    all active slots, (3) retire slots whose request hit its decode budget.
    The loop never syncs token values — greedy decode to a fixed budget
    makes scheduling token-value-independent, so device token arrays are
    stacked and fetched once at report time (``simulate_only=True`` skips
    model compute entirely for million-request capacity studies).
    """

    def __init__(self, cfg: ModelConfig | None = None, *, slots: int,
                 prompt_len: int, decode_tokens: int, max_len: int | None = None,
                 seed: int = 0, cost_model: ServeCostModel | None = None,
                 attn_impl: str = "auto", block_k: int | None = None,
                 simulate_only: bool = False, params: Any = None):
        if slots < 1:
            raise ValueError("need at least one slot")
        if decode_tokens < 1:
            raise ValueError("decode_tokens must be >= 1")
        self.cfg = cfg
        self.slots = slots
        self.prompt_len = prompt_len
        self.decode_tokens = decode_tokens
        self.max_len = max_len or (prompt_len + decode_tokens + 1)
        self.cost = cost_model or ServeCostModel()
        self.simulate_only = simulate_only
        if not simulate_only:
            if cfg is None:
                raise ValueError("cfg required unless simulate_only=True")
            api = get_model(cfg)
            if api.prefill_slots is None and not api.gqa_arena:
                raise ValueError(f"family {cfg.family!r} has no serving path")
            self.params = (params if params is not None
                           else init_params(cfg, seed))
            self.arena = init_arena(cfg, slots, self.max_len)
            self._tok = jnp.zeros((slots,), jnp.int32)
            # The arena's state goes in apart from ``lengths`` (callers may
            # still hold that), so a model that asks can have the state
            # donated and updated in place.
            donate = (3,) if api.donate_state else ()
            prefill = jax.jit(
                lambda p, t, sids, state, n: arena_prefill(
                    p, t, sids, {**state, "lengths": n}, cfg),
                donate_argnums=donate, keep_unused=True)
            decode = jax.jit(
                lambda p, tok, act, state, n: arena_decode(
                    p, tok, act, {**state, "lengths": n}, cfg,
                    attn_impl=attn_impl, block_k=block_k),
                donate_argnums=donate, keep_unused=True)
            self._prefill = lambda p, t, sids, ar: prefill(
                p, t, sids, *_state_and_lengths(ar))
            self._decode = lambda p, tok, act, ar: decode(
                p, tok, act, *_state_and_lengths(ar))
            # Jitted so the drop-mode sentinel is a traced constant; the
            # eager .at[].set ships it as a runtime scalar, an implicit
            # h2d that would trip the @hot_path transfer guard.
            self._scatter_tok = jax.jit(
                lambda tok, sids, first: tok.at[sids].set(
                    first, mode="drop"))
        self.queue: collections.deque[RequestRecord] = collections.deque()
        self.records: list[RequestRecord] = []
        self.slot_owner: list[RequestRecord | None] = [None] * slots
        self._free = list(range(slots))
        heapq.heapify(self._free)
        self.busy_until = 0.0
        self.iterations: list[IterationStats] = []
        # Deferred token materialization: (kind, owners, device (slots,) i32).
        self._events: list[tuple[str, list, jax.Array]] = []

    # -- request intake ------------------------------------------------------
    def submit(self, request_id: int, prompt: np.ndarray | None,
               t: float) -> RequestRecord:
        if not self.simulate_only:
            prompt = np.asarray(prompt, np.int32)[: self.prompt_len]
        rec = RequestRecord(request_id=request_id, arrival_t=t, prompt=prompt)
        self.queue.append(rec)
        self.records.append(rec)
        return rec

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(o is not None for o in self.slot_owner)

    @property
    def n_active(self) -> int:
        return sum(o is not None for o in self.slot_owner)

    # -- one iteration -------------------------------------------------------
    @hot_path
    def step(self, t: float) -> float:
        """Run one iteration starting at virtual time ``t``; returns its
        duration (cost-model virtual seconds).

        ``@hot_path`` (here and on ``_step``, which lint rule R003 scans):
        the decode loop must never host-sync per iteration — token
        materialization is deferred to :meth:`_materialize_tokens` (one sync
        for the whole run), and every h2d transfer here is an explicit
        ``jnp.asarray``.
        """
        step = len(self.iterations)
        with tracing.span("serve.step", step=step):
            return self._step(t, step)

    @hot_path
    def _step(self, t: float, step: int) -> float:
        admitted: list[RequestRecord] = []
        while self.queue and self._free:
            slot = heapq.heappop(self._free)
            rec = self.queue.popleft()
            rec.slot = slot
            rec.start_t = t
            self.slot_owner[slot] = rec
            admitted.append(rec)
        dur = 0.0
        if admitted:
            dur += self.cost.prefill_s(len(admitted))
            for rec in admitted:
                rec.first_token_t = t + dur
            if not self.simulate_only:
                toks = np.zeros((self.slots, self.prompt_len), np.int32)
                sids = np.full((self.slots,), self.slots, np.int32)
                for i, rec in enumerate(admitted):
                    toks[i, : len(rec.prompt)] = rec.prompt
                    sids[i] = rec.slot
                with tracing.span("serve.prefill", step=step,
                                  family=self.cfg.family):
                    sids_dev = jnp.asarray(sids)
                    first, self.arena = self._prefill(
                        self.params, jnp.asarray(toks), sids_dev, self.arena)
                    self._tok = self._scatter_tok(self._tok, sids_dev, first)
                self._events.append(("prefill", list(admitted), first))
        active = [o is not None for o in self.slot_owner]
        n_active = sum(active)
        if n_active:
            dur += self.cost.decode_s(n_active)
            if not self.simulate_only:
                # Host-built bool mask, then one explicit dtype-preserving
                # device_put (an eager dtype conversion would count as an
                # implicit transfer under the guard).
                act_host = np.fromiter(active, np.bool_, count=self.slots)
                with tracing.span("serve.decode", step=step,
                                  family=self.cfg.family):
                    nxt, self.arena = self._decode(
                        self.params, self._tok,
                        jnp.asarray(act_host), self.arena)
                self._tok = nxt
                self._events.append(("decode", list(self.slot_owner), nxt))
            end = t + dur
            for s, rec in enumerate(self.slot_owner):
                if rec is None:
                    continue
                rec.decoded += 1
                if rec.decoded >= self.decode_tokens:
                    rec.finish_t = end
                    self.slot_owner[s] = None
                    heapq.heappush(self._free, s)
        self.iterations.append(IterationStats(
            t=t, duration_s=dur, admitted=len(admitted),
            n_active=n_active, queue_depth=len(self.queue)))
        return dur

    # -- results -------------------------------------------------------------
    def _materialize_tokens(self) -> None:
        """One host sync for ALL buffered per-iteration token arrays."""
        if not self._events:
            return
        host = np.asarray(jnp.stack([ev[2] for ev in self._events]))
        for (kind, owners, _), row in zip(self._events, host):
            if kind == "prefill":
                for i, rec in enumerate(owners):
                    rec.tokens.append(int(row[i]))
            else:
                for s, rec in enumerate(owners):
                    if rec is not None:
                        rec.tokens.append(int(row[s]))
        self._events.clear()

    def report(self, *, horizon_s: float | None = None) -> ServingReport:
        self._materialize_tokens()
        if horizon_s is None:
            horizon_s = max((r.finish_t for r in self.records
                             if r.finish_t is not None), default=0.0)
        return ServingReport(records=list(self.records), horizon_s=horizon_s)


# --------------------------------------------------------------------------- #
# VirtualClock adapter
# --------------------------------------------------------------------------- #
class ContinuousServer:
    """DeviceFlow delivery callback driving an engine on the shared clock.

    Arrivals enqueue into the engine; a self-rescheduling *tick* event runs
    one engine iteration whenever work is pending, so queued requests join
    at exactly the next iteration boundary and the engine idles only when
    the queue and every slot are empty.  Use as ``DeviceFlow(server)`` with
    ``server = ContinuousServer(engine, flow.clock)``.
    """

    def __init__(self, engine: ContinuousBatchingEngine, clock, *,
                 prompt_of: Callable[[Any], np.ndarray] | None = None):
        self.engine = engine
        self.clock = clock
        self.prompt_of = prompt_of
        self._armed = False

    def _prompt(self, message) -> np.ndarray | None:
        if self.engine.simulate_only:
            return None
        if self.prompt_of is not None:
            return self.prompt_of(message)
        payload = message.payload
        if hasattr(payload, "materialize"):  # UpdateHandle
            payload = payload.materialize()
        return np.asarray(payload["tokens"])

    def __call__(self, d) -> None:
        msgs = (d.batch.messages() if getattr(d, "batch", None) is not None
                else [d.message])
        for m in msgs:
            self.engine.submit(m.device_id, self._prompt(m), d.t)
        self._kick(d.t)

    def _kick(self, t: float) -> None:
        if self._armed:
            return
        self._armed = True
        self.clock.schedule(max(t, self.engine.busy_until), self._tick)

    def _tick(self) -> None:
        t = self.clock.now
        dur = self.engine.step(t)
        self.engine.busy_until = t + dur
        if self.engine.has_work:
            self.clock.schedule(self.engine.busy_until, self._tick)
        else:
            self._armed = False
