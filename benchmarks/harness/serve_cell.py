"""A serving cell (`kind: closed_loop` or `open_loop`): `init_serving(...)`,
the engine on its own driver thread (`start()`), the callers as threads of
this harness, each timing its own `submit()` and every token `stream()`
hands it.

- `serve_tok_s`: tokens whose arrival fell inside the window, over the
  window (not requests completed in it, so a request that straddles an edge
  is not a step in the number).
- `ttft_p25_ms`, `itl_p50_ms`, `itl_p95_ms`: from this harness's clock, for
  requests submitted inside the window; open loop times from when a request
  was due. Which of them a cell reports is BENCHMARK.json's choice.
- `prefill_tok_s` (a counter, for the per-layer `serve_prefill_tok_s`): prompt
  tokens taken in inside the window, over the window. The harness sees a
  request's `submit()` and its first token, not each chunk, so a prompt
  counts by the share of its submit-to-first-token interval inside the
  window. It spreads by 4.5% between seeds: not an end-to-end metric.

The loop is already turning when the window opens (`warm_loop_s` of the same
traffic, which also compiles the cell's two programs: part of set-up).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import stats, traffic as traffic_mod
from .program import (build_model, place_held_experts, program_seed,
                      reference_args, reference_module)


# some tens of decode iterations, and as a rule a prefill chunk or two
TRACE_SECONDS = 3.0


@dataclasses.dataclass
class Record:
    index: int                      # position in the order sent
    prompt_len: int
    new_tokens: int
    prompt: np.ndarray
    due_time: Optional[float] = None        # open loop only
    submit_time: float = 0.0
    submit_returned: float = 0.0    # how long submit() itself held the caller
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    state: str = "sent"             # finished | cut (by the harness) | error
    error: Optional[str] = None

    @property
    def clock_start(self) -> float:
        return self.due_time if self.due_time is not None else self.submit_time


class Load:
    """The callers, `clients` threads. Closed loop: each cycles through its
    own sequence of requests, sending the next when the last finished. Open
    loop: each takes the next request of the seed's order when it is due."""

    def __init__(self, serving, traffic: Dict[str, Any], seed: int,
                 vocab: int, horizon_s: float):
        self.serving, self.t, self.seed, self.vocab = (serving, traffic, seed,
                                                       vocab)
        self.open_loop = traffic["kind"] == "open_loop"
        self.requests = (traffic_mod.request_set(traffic, seed)
                         if self.open_loop
                         else traffic_mod.client_sequences(traffic, seed))
        self.records: List[Record] = []
        self.handles: Dict[int, Any] = {}
        self.lock = threading.Lock()
        self.closing = threading.Event()    # no new requests
        self.next_index = 0
        self.t0 = None
        self.due = None
        if self.open_loop:
            self.due = traffic_mod.arrival_times(traffic["arrivals"],
                                                 horizon_s)
        self.threads = [threading.Thread(target=self._client, args=(c,),
                                         daemon=True,
                                         name=f"bench-client-{c}")
                        for c in range(int(traffic["clients"]))]

    def start(self) -> None:
        self.t0 = time.perf_counter()
        for th in self.threads:
            th.start()

    def _take(self, client: int, turn: int) -> Optional[Record]:
        with self.lock:
            i = self.next_index
            if self.due is not None and i >= len(self.due):
                return None
            self.next_index += 1
        if self.open_loop:
            spec = self.requests[i % len(self.requests)]
        else:
            mine = self.requests[client]
            spec = mine[turn % len(mine)]
        rec = Record(i, spec.prompt_len, spec.new_tokens,
                     traffic_mod.prompt_ids(self.seed, i, spec.prompt_len,
                                            self.vocab))
        if self.due is not None:
            rec.due_time = self.t0 + self.due[i]
        return rec

    def _client(self, client: int) -> None:
        from jax.profiler import TraceAnnotation

        sampling = self.t.get("sampling", {})
        turn = -1
        while not self.closing.is_set():
            turn += 1
            rec = self._take(client, turn)
            if rec is None:
                return
            if rec.due_time is not None:
                wait = rec.due_time - time.perf_counter()
                if wait > 0 and self.closing.wait(wait):
                    return
            try:
                with TraceAnnotation("serve/submit"):
                    rec.submit_time = time.perf_counter()
                    handle = self.serving.submit(
                        rec.prompt, max_new_tokens=rec.new_tokens,
                        temperature=float(sampling.get("temperature", 0.0)))
                    rec.submit_returned = time.perf_counter()
                with self.lock:
                    self.records.append(rec)
                    self.handles[rec.index] = handle
                for tok in handle.stream(timeout_s=300.0):
                    with TraceAnnotation("serve/token"):
                        rec.token_times.append(time.perf_counter())
                        rec.tokens.append(int(tok))
                rec.state = ("finished" if handle.state == "finished"
                             else "cut")
            except Exception as e:      # a caller reports, the run goes on
                rec.state, rec.error = "error", repr(e)
                with self.lock:
                    if rec not in self.records:
                        self.records.append(rec)
            finally:
                with self.lock:
                    self.handles.pop(rec.index, None)

    def stop(self) -> None:
        """No new requests; what is in flight is cut (the harness's own
        decision, not a failure); wait for every caller."""
        self.closing.set()
        with self.lock:
            handles = list(self.handles.values())
        for h in handles:
            h.cancel()
        for th in self.threads:
            th.join(timeout=60.0)
        alive = [th.name for th in self.threads if th.is_alive()]
        if alive:
            raise RuntimeError(f"callers did not end: {alive}")


def prompt_tokens_inside(records: List[Record], t_open: float,
                         t_close: float) -> float:
    """Prompt tokens taken in between `t_open` and `t_close`: each request's
    prompt by the share of its submit-to-first-token interval inside."""
    total = 0.0
    for r in records:
        if not r.token_times:
            continue
        a, b = r.clock_start, r.token_times[0]
        inside = min(b, t_close) - max(a, t_open)
        if inside > 0 and b > a:
            total += r.prompt_len * inside / (b - a)
    return total


def serving_config(cell, devs):
    from deepspeed_tpu.inference.kv_cache import paged_cache_memory_bytes
    from deepspeed_tpu.serving import ServingConfig
    import jax.numpy as jnp

    s = cell.config["serving"]
    shape = {k: int(s[k]) for k in ("block_size", "max_seqs", "prefill_chunk",
                                    "max_model_len")}

    def num_blocks(model_config):
        if "num_blocks" in s:
            return int(s["num_blocks"])
        limit = devs[0].memory_stats()["bytes_limit"]
        per_block = paged_cache_memory_bytes(
            model_config, 1, shape["block_size"],
            getattr(jnp, cell.config["model"]["dtype"]))
        return int(float(s["arena_share_of_chip"]) * limit) // per_block

    return lambda mc: ServingConfig(num_blocks=num_blocks(mc), **shape)


def run(cell, seed: int, seconds: float, tracer, devs, counter,
        setup) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import InferenceConfig

    t = cell.traffic
    model = build_model(cell)
    cfg = model.config
    dtype = getattr(jnp, cell.config["model"]["dtype"])
    serving = deepspeed_tpu.init_serving(
        model=model, serving_config=serving_config(cell, devs)(cfg),
        config=InferenceConfig(dtype=dtype, seed=program_seed(seed)))
    setup.mark("engine")
    # every serving program is called with `engine.params` as it then reads
    placed = place_held_experts(cell, serving.engine.params, seed,
                                cfg.vocab_size)
    if placed is not None:
        serving.engine.params = placed
        setup.mark("placement")
    warm_s = float(t["warm_loop_s"])
    load = Load(serving, t, seed, cfg.vocab_size,
                horizon_s=warm_s + seconds + 600.0)
    problems: List[str] = []
    try:
        serving.start()
        load.start()
        # the loop turns: first until every program it needs is compiled
        # (a first token has come back and a decode step after it), then
        # for warm_loop_s more
        deadline = time.perf_counter() + 1100.0
        while time.perf_counter() < deadline:
            with load.lock:
                ready = any(len(r.token_times) >= 3 for r in load.records)
            if ready:
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("no request produced tokens during warm-up")
        setup.mark("first_tokens")
        time.sleep(warm_s)

        counter.arm()
        setup_s = setup.done("warm_loop")
        t_open = time.perf_counter()
        t_close = t_open + seconds
        while True:
            now = time.perf_counter()
            if now >= t_close:
                break
            tracer.maybe_start(now - t_open)
            tracer.maybe_stop(now - t_open)
            time.sleep(min(0.05, t_close - now))
        tracer.stop()
        counter.disarm()
        # the tail of all requests: those sent inside the window keep their
        # callers until their first token has come (bounded)
        load.closing.set()
        wait_until = time.perf_counter() + 30.0
        while time.perf_counter() < wait_until:
            with load.lock:
                pending = [r for r in load.records
                           if t_open <= r.submit_time < t_close
                           and not r.token_times and r.state == "sent"]
            if not pending:
                break
            time.sleep(0.02)
        load.stop()
    finally:
        load.closing.set()
        serving.stop()

    records = sorted(load.records, key=lambda r: r.index)
    measured = [r for r in records if t_open <= r.submit_time < t_close]
    in_window = sum(1 for r in records for x in r.token_times
                    if t_open <= x < t_close)
    ttft = [(r.token_times[0] - r.clock_start) * 1e3
            for r in measured if r.token_times]
    gaps = [(b - a) * 1e3 for r in measured
            for a, b in zip(r.token_times, r.token_times[1:])]
    late = [(r.submit_time - r.due_time) * 1e3 for r in measured
            if r.due_time is not None]
    failed = 0
    for r in measured:
        bad = None
        if r.state == "error":
            bad = r.error
        elif not r.token_times:
            bad = "no token came"
        elif r.state == "finished" and len(r.tokens) != r.new_tokens:
            bad = f"{len(r.tokens)} of {r.new_tokens} tokens"
        elif not all(0 <= x < cfg.vocab_size for x in r.tokens):
            bad = "token out of range"
        if bad:
            failed += 1
            problems.append(f"request {r.index}: {bad}")
    finished = [r for r in measured if r.state == "finished"]
    if not finished:
        problems.append("no request sent inside the window finished")
    if not ttft or len(gaps) < 20:
        problems.append(f"too few samples: {len(ttft)} first tokens, "
                        f"{len(gaps)} gaps")

    # one served sequence: log-probabilities through the paged kernels
    # against the plain float32 reference, outside the window
    ref = t["reference"]
    seq = got = diff = None
    if finished:
        cap = int(ref.get("max_tokens", 1024))
        fits = [r for r in finished if r.prompt_len + r.new_tokens <= cap]
        r = max(fits or finished[:1], key=lambda r: r.prompt_len + r.new_tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        got = serving.score_logprobs(seq)
    params = serving.engine.params
    serving.close()
    # the arena lives as long as the engine object: let both go, so that the
    # reference's float32 pass has the chip beside the weights alone
    del serving, load
    gc.collect()
    if seq is not None:
        reference, ref_args = reference_module(cell), reference_args(cell)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(
                lambda p, ids: reference.next_token_logprobs(
                    p, ids, **ref_args))(params, seq[None]))[0]
        diff = float(np.abs(got - want).max())
        if not np.isfinite(got).all() or not diff <= float(ref["logprob_atol"]):
            problems.append(f"served log-probs differ from the reference by "
                            f"{diff:.4f} over {len(seq)} tokens (tolerance "
                            f"{ref['logprob_atol']})")

    e2e = {"setup_s": setup_s, "serve_tok_s": in_window / seconds}
    prefill_tok_s = prompt_tokens_inside(records, t_open, t_close) / seconds
    if ttft:
        e2e["ttft_p25_ms"] = stats.percentile(ttft, 25)
    if gaps:
        e2e["itl_p50_ms"] = stats.percentile(gaps, 50)
        e2e["itl_p95_ms"] = stats.percentile(gaps, 95)
    print(json.dumps({
        "requests_in_window": len(measured), "finished": len(finished),
        "ttft_samples": len(ttft), "gap_samples": len(gaps),
        "tokens_in_window": in_window,
        # judged or not, every statistic of the window, for the next reader
        "window": dict(e2e, prefill_tok_s=prefill_tok_s,
                       ttft_p50_ms=stats.percentile(ttft, 50)
                       if ttft else None,
                       ttft_p95_ms=stats.percentile(ttft, 95)
                       if ttft else None),
        "ttft_ms_sorted": [round(x, 1) for x in sorted(ttft)],
        "submit_call_ms_p50_p95": [
            round(stats.percentile([(r.submit_returned - r.submit_time) * 1e3
                                    for r in measured], q), 2)
            for q in (50, 95)] if measured else None,
        "generator_late_p95_ms": stats.percentile(late, 95) if late else None,
        "reference_logprob_maxdiff": diff}), flush=True)
    # every request that touched the window, times in seconds from its
    # opening: [prompt, asked, submit, first token, last token, tokens got]
    print(json.dumps({"requests": [
        [r.prompt_len, r.new_tokens, round(r.submit_time - t_open, 4),
         round(r.token_times[0] - t_open, 4),
         round(r.token_times[-1] - t_open, 4), len(r.tokens)]
        for r in records if r.token_times and r.token_times[-1] >= t_open
        and r.submit_time < t_close]}), flush=True)
    return {
        "problems": problems, "attempted": len(measured), "failed": failed,
        "end_to_end": e2e,
        "counters": {"compiles_in_window": counter.count,
                     "prefill_tok_s": prefill_tok_s},
        "model_config": cfg, "records": records,
    }
