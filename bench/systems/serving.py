"""Continuous-batching LM serving on the program's normal path.

Build: the configuration's model is a ``repro`` ``ModelConfig``; its weights
come from the reference module's ``init_weights`` (made on the chip from
the seed) and go to a ``ContinuousBatchingEngine`` with the configured
slots, prompt length and decode budget.  Prompts are uniform token ids drawn
from the seed.

Traffic, one generator for every mix:

* ``kind: serve_closed``: a standing backlog.  Before each engine step the
  queue is topped up to ``backlog`` requests, so slots refill as they
  retire.  The harness waits on the step ``ahead_steps`` behind the newest,
  so the chip stays fed while the host stalls.  Reports
  ``serve_tokens_per_s``.
* ``kind: serve_open``: an open loop.  Arrival times come from the seed
  (``arrivals.process``: ``poisson`` at ``rate_per_s``, or ``on_off``:
  bursts of ``on_s`` seconds at ``rate_per_s * (on_s + off_s) / on_s``
  separated by ``off_s`` silent seconds, the same mean rate).  Arrivals
  start ``lead_s`` before the window, in set-up, so the window opens in
  steady state; they are drawn up to ``horizon_s`` past it.
  Each request is timed from its scheduled time.  Reports
  ``serve_ttft_p90_ms`` and ``serve_itl_p99_ms``.

In the open loop the harness blocks on each step's tokens, so every token
has the host time at which its step ended.  Steps start while the window is
open; the window closes when every step started in it has ended.

Check: the slots are split into ``check_requests`` equal groups (at most
one a slot), and from each, requests that the window finished are drawn
from the seed, ``check_requests`` in all, so the sample spans the batch.  The reference runs over each prompt with its
served tokens; at each position, the gap is how far the served token's
reference logit lies below the reference's best.  ``max_logit_gap`` is the
widest gap, ``mean_logit_gap`` the mean over every position; the check
compares those that the configuration's ``limits`` name.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.serving import ContinuousBatchingEngine


def arrival_times(arrivals: dict, start: float, end: float,
                  order: np.random.Generator) -> np.ndarray:
    """Scheduled arrival times in ``[start, end)``.  The gaps between
    arrivals are one fixed draw (``gap_seed``), put in an order drawn from
    the run's seed: every seed offers the same load, in another order."""
    rate, fixed = arrivals["rate_per_s"], np.random.default_rng(
        arrivals["gap_seed"])
    n = int((end - start) * rate * 1.5) + 16
    if arrivals["process"] == "poisson":
        t = start + np.cumsum(order.permutation(fixed.exponential(1 / rate, n)))
        return t[t < end]
    if arrivals["process"] == "on_off":
        on, off = arrivals["on_s"], arrivals["off_s"]
        burst = rate * (on + off) / on
        gaps = order.permutation(fixed.exponential(1 / burst, n))
        out = []
        for t in start + np.cumsum(gaps):
            # Time inside bursts only: each on_s of it is followed by off_s.
            t_on = t - start
            t_abs = start + t_on + (t_on // on) * off
            if t_abs >= end:
                break
            out.append(t_abs)
        return np.asarray(out)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def pct(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class System:
    def __init__(self, config: dict, traffic: dict, *, seed: int, devices,
                 spans, reference, overrides: dict):
        self.cfg = {**config, **overrides.get("config", {})}
        self.model = {**self.cfg["model"], **overrides.get("model", {})}
        self.weights = {**self.cfg["weights"], **overrides.get("weights", {})}
        self.traffic = {**traffic, **overrides.get("traffic", {})}
        self.seed = seed
        self.devices = devices
        self.spans = spans
        self.ref = reference
        # Independent streams, so that what one draws does not depend on
        # how many requests another timing let through.
        self.rng_prompts = np.random.default_rng([seed, 0])
        self.rng_arrivals = np.random.default_rng([seed, 1])
        self.rng_sample = np.random.default_rng([seed, 2])

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        tr = self.traffic
        with self.spans("setup.weights"):
            self.params = self.ref.init_weights(
                self.model, self.seed, std=self.weights["init_std"])
            jax.block_until_ready(self.params)
        self.engine = ContinuousBatchingEngine(
            ModelConfig(**self.model), slots=tr["slots"],
            prompt_len=tr["prompt_len"], decode_tokens=tr["decode_tokens"],
            params=self.params, attn_impl=self.cfg["attn_impl"])
        self.clock0 = time.perf_counter()
        self.next_id = 0
        self.sched: dict[int, float] = {}  # request id -> scheduled time
        self.token_t: dict[int, list[float]] = {}  # request id -> token times
        self.steps: list[tuple] = []  # (t0, t1, tokens, decode lengths)
        self.live: dict = {}  # request id -> [record, decode tokens seen]
        # Steps dispatched and not yet waited on (their ``lengths``).
        self.pending: collections.deque = collections.deque()
        self.ahead = (tr.get("ahead_steps", 0) if tr["kind"] == "serve_closed"
                      else 0)
        with self.spans("setup.warmup"):
            # One request through prefill and decode compiles every shape
            # the traffic uses; it stays in its slot and serves on.
            self.submit(self.now())
            self.step()
            self.step()
            self.start_arrivals()
            self.drain()

    def now(self) -> float:
        return time.perf_counter() - self.clock0

    def submit(self, t_sched: float) -> None:
        rid = self.next_id
        self.next_id += 1
        prompt = self.rng_prompts.integers(0, self.model["vocab_size"],
                                   self.traffic["prompt_len"])
        self.sched[rid] = t_sched
        self.token_t[rid] = []
        self.engine.submit(rid, prompt, t_sched)

    def step(self) -> None:
        eng = self.engine
        t0 = self.now()
        queued = list(eng.queue)
        lengths = [self.traffic["prompt_len"] + r.decoded + 1
                   for r in eng.slot_owner if r is not None]
        with self.spans("serve.step"):
            eng.step(t0)
        self.pending.append(eng.arena["lengths"])
        with self.spans("serve.wait"):
            while len(self.pending) > self.ahead:
                jax.block_until_ready(self.pending.popleft())
        t1 = self.now()
        for rec in queued:
            if rec.slot is not None and rec.request_id not in self.live:
                self.live[rec.request_id] = [rec, 0]
                self.token_t[rec.request_id].append(t1)  # prefill token
                lengths.append(self.traffic["prompt_len"] + 1)
        tokens = 0
        for rid, (rec, seen) in list(self.live.items()):
            new = rec.decoded - seen
            self.token_t[rid].extend([t1] * new)
            self.live[rid][1] = rec.decoded
            tokens += new
            if rec.finish_t is not None:
                del self.live[rid]
        admitted = sum(1 for r in queued if r.slot is not None)
        self.steps.append((t0, t1, tokens + admitted, admitted, lengths))

    def drain(self) -> None:
        with self.spans("serve.wait"):
            while self.pending:
                jax.block_until_ready(self.pending.popleft())

    def serve_until(self, t_stop: float) -> None:
        """Run the loop until ``t_stop``: top up the backlog (closed) or
        submit every arrival that is due (open), step while there is work,
        sleep until the next arrival when there is none."""
        tr, arrivals = self.traffic, self.arrivals
        while (now := self.now()) < t_stop:
            if tr["kind"] == "serve_closed":
                while len(self.engine.queue) < tr["backlog"]:
                    self.submit(now)
            busy_until = self.steps[-1][1]
            while self.nxt < len(arrivals) and arrivals[self.nxt] <= now:
                # How late the loop took the arrival, beyond its own step.
                self.lateness.append(now - max(arrivals[self.nxt], busy_until))
                self.submit(float(arrivals[self.nxt]))
                self.nxt += 1
            if self.engine.has_work:
                self.step()
            else:
                wake = arrivals[self.nxt] if self.nxt < len(arrivals) else t_stop
                time.sleep(max(0.0, min(wake, t_stop) - self.now()))

    def start_arrivals(self) -> None:
        """Open loop: arrivals start ``lead_s`` before the window."""
        tr = self.traffic
        self.arrivals, self.nxt, self.lateness = np.zeros(0), 0, []
        if tr["kind"] == "serve_open":
            t_a = self.now()
            self.arrivals = arrival_times(
                tr["arrivals"], t_a, t_a + tr["lead_s"] + tr["horizon_s"],
                self.rng_arrivals)
            self.serve_until(t_a + tr["lead_s"])

    # -- measured window ----------------------------------------------------
    def window(self, seconds: float) -> dict:
        w0 = self.now()
        w1 = w0 + seconds
        first_step = len(self.steps)
        self.lateness = []
        self.serve_until(w1)
        self.drain()  # every step sent in the window has ended
        steps = self.steps[first_step:]
        t_end = max(self.now(), w1)
        span = t_end - w0
        notes, metrics = [], {}
        if self.traffic["kind"] == "serve_closed":
            metrics["serve_tokens_per_s"] = sum(s[2] for s in steps) / span
            due = [r for r, t in self.sched.items() if t >= w0]
        else:
            due = [r for r, t in self.sched.items() if w0 <= t < w1]
            ttft = [(self.token_t[r][0] if self.token_t[r] else t_end)
                    - self.sched[r] for r in due]
            gaps = [b - a for r in self.token_t
                    for a, b in zip(self.token_t[r], self.token_t[r][1:])
                    if w0 <= b <= t_end]
            metrics["serve_ttft_p90_ms"] = pct(ttft, 90) * 1e3
            metrics["serve_itl_p99_ms"] = pct(gaps, 99) * 1e3
            late = self.lateness or [0.0]
            notes.append(
                f"open loop: {len(due)} requests due in the window, "
                f"{len(gaps)} token gaps; generator lateness max "
                f"{max(late) * 1e3:.3f} ms, p95 {pct(late, 95) * 1e3:.3f} ms")
        n_tokens = self.traffic["decode_tokens"] + 1
        self.finished = [r for r in self.token_t
                         if len(self.token_t[r]) == n_tokens
                         and w0 <= self.token_t[r][-1] <= t_end]
        counters = {"steps": steps, "span_s": span, "window": (w0, t_end)}
        return {"metrics": metrics, "attempted": len(due), "failed": 0,
                "counters": counters, "notes": notes}

    # -- check --------------------------------------------------------------
    def release(self) -> None:
        """Fetch every token once, keep the sampled requests (finished
        requests from each group of slots), free the engine and its KV
        arena."""
        report = self.engine.report()
        by_id = {r.request_id: r for r in report.records}
        k, slots = self.traffic["check_requests"], self.traffic["slots"]
        n = min(k, slots)
        groups: dict[int, list[int]] = {}
        for rid in self.finished:
            groups.setdefault(by_id[rid].slot * n // slots, []).append(rid)
        pick = []
        for i, (_, g) in enumerate(sorted(groups.items())):
            for _ in range(min(k // n + (i < k % n), len(g))):
                pick.append(g.pop(self.rng_sample.integers(len(g))))
        self.sample = [(np.asarray(by_id[r].prompt),
                        np.asarray(by_id[r].tokens)) for r in pick]
        del self.engine, report, by_id
        gc.collect()

    def gaps(self, mode: str = "f32") -> list[np.ndarray]:
        """Per sampled request, at each position that produced a served
        token: how far below the reference's best that token's reference
        logit lies.  With ``mode="fp8"`` (the control) the token is the one
        the fp8 reference ranks first instead of the served one."""
        out = []
        for prompt, tokens in self.sample:
            seq = np.concatenate([prompt, tokens[:-1]])
            ref = self.ref.logits(self.params, seq, self.model)
            pos = np.arange(len(prompt) - 1, len(seq))
            if mode == "f32":
                tok = tokens
            else:
                low = self.ref.logits(self.params, seq, self.model, mode=mode)
                tok = np.asarray(low[pos].argmax(-1))
            ref = np.asarray(ref[pos], np.float64)
            out.append(ref.max(-1) - ref[np.arange(len(pos)), tok])
        return out

    @staticmethod
    def summary(gaps: list[np.ndarray]) -> dict:
        if not gaps:
            return {"max_logit_gap": float("nan"),
                    "mean_logit_gap": float("nan")}
        g = np.concatenate(gaps)
        return {"max_logit_gap": float(g.max()),
                "mean_logit_gap": float(g.mean())}

    def check(self) -> dict:
        limits = self.cfg["limits"]
        if not self.sample:
            return {"finished_sampled": {"value": float("inf"),
                                         "limit": min(limits.values())}}
        got = self.summary(self.gaps())
        return {k: {"value": got[k], "limit": v} for k, v in limits.items()}

    def control(self) -> dict:
        """The gaps of the tokens that the fp8 reference puts first, at the
        same positions of the same sampled requests."""
        return self.summary(self.gaps(mode="fp8"))
