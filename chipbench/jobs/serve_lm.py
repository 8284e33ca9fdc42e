"""The served path as a user of the library sets it up, all in this process
(a chip belongs to one process): ``ServingClient`` -> ``ServingFrontend`` ->
one ``ServingWorker`` -> ``ServingEngine`` with ``ServingConfig`` defaults
(only ``max_context`` comes from the mix). No environment knob is set.

Closed loop: ``clients`` threads each send a request from the seeded list,
wait for the reply and send the next at once. The loop starts before the
window; the window opens once every client has a request decoding, on the
completion of an engine step, and closes on the first step completed after
``--seconds`` — so its two edges cut no step in half.
"""

from __future__ import annotations

import statistics
import threading
import time
from typing import Dict, List

import numpy as np

from .. import harness, reference, traffic

#: (b) every generated token's reference logit against that position's
#: largest reference logit. The program picks the argmax of logits computed
#: with bf16 operands and a bf16 residual stream (rms error about 3% of the
#: logits' rms of ~0.65, so ~0.02 absolute a logit, see train_lm.py); the
#: argmax of noisy logits can sit below the true maximum by a few such
#: errors at most. With random weights the top logits are ~0.03 apart, so
#: the token itself is not comparable, its logit is. A wrong position,
#: a wrong cache row or a dropped layer picks a token whose reference logit
#: is an ordinary one: about 4 sigma = 2.6 below the maximum.
ARGMAX_LOGIT_TOL = 0.25

#: host spans the traced run wraps around the engine's steps, by the
#: attribute each wraps (only where the attribute exists)
HOST_SPANS = {"kv_gather_host": ("cache", "gather"),
              "kv_append_host": ("cache", "append"),
              "prefill_call": (None, "_jit_prefill"),
              "decode_call": (None, "_jit_decode")}


def annotate(engine) -> None:
    """Wrap four callables of ``engine`` in ``TraceAnnotation`` so that an
    idle gap of the device can be named after what the host was doing."""
    import jax

    for span, (owner, attr) in HOST_SPANS.items():
        target = getattr(engine, owner, None) if owner else engine
        fn = getattr(target, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span=span, **kw):
            with jax.profiler.TraceAnnotation(_span):
                return jax.block_until_ready(_fn(*a, **kw))

        setattr(target, attr, wrapped)


def counters() -> Dict[str, dict]:
    """The program's serving counters as ``{name: {label: (sum, count)}}``
    (a plain counter's count is None)."""
    from horovod_tpu.metrics import instruments

    out = {}
    for name, make in (("serving_phase_seconds",
                        instruments.serving_phase_seconds),
                       ("serving_decode_batch",
                        instruments.serving_decode_batch),
                       ("serving_request_latency",
                        instruments.serving_request_latency),
                       ("serving_tokens", instruments.serving_tokens)):
        out[name] = {
            ",".join(v for _, v in key): ((h, None) if isinstance(h, float)
                                          else (h.sum, h.count))
            for key, h in make().snapshot_values().items()}
    return out


def counters_delta(before, after) -> Dict[str, dict]:
    out = {}
    for name, labels in after.items():
        out[name] = {}
        for label, (s, n) in labels.items():
            s0, n0 = before.get(name, {}).get(label, (0.0, 0 if n is not None
                                                      else None))
            out[name][label] = (s - s0, None if n is None else n - n0)
    return out


class ClosedLoop:
    """``clients`` threads over one ``ServingClient``; each takes the next
    request of the seeded list (round again at its end, under a new id),
    waits for its reply, and goes on."""

    def __init__(self, client, requests: List[dict], clients: int,
                 timeout: float):
        self.client, self.requests = client, requests
        self.timeout = timeout
        self.lock = threading.Lock()
        self.next = 0
        self.done: List[dict] = []       # completed, in completion order
        self.errors: List[str] = []
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._client, daemon=True,
                                         name=f"chipbench-client-{i}")
                        for i in range(clients)]

    def start(self):
        for t in self.threads:
            t.start()

    def _client(self):
        while not self.stop.is_set():
            with self.lock:
                index = self.next
                self.next += 1
            req = self.requests[index % len(self.requests)]
            fut = self.client.submit(req["prompt"], req["new"],
                                     request_id=f"r{index}")
            try:
                tokens = fut.result(timeout=self.timeout)
            except (RuntimeError, TimeoutError) as exc:
                if not self.stop.is_set():
                    with self.lock:
                        self.errors.append(f"r{index}: {exc}")
                continue
            with self.lock:
                self.done.append({"index": index, "tokens": tokens,
                                  "asked": req["new"], "done_t": fut.done_t,
                                  "latency_s": fut.client_latency()})

    def finish(self):
        """Stop after the requests in flight (they are cancelled)."""
        self.stop.set()
        for i in range(self.next):
            self.client.cancel(f"r{i}", "benchmark window over")
        for t in self.threads:
            t.join(timeout=30)


def steps_done() -> int:
    """Engine phases (a prefill, or one batched decode) completed so far:
    the program observes ``serving_phase_seconds`` as each one ends."""
    from horovod_tpu.metrics import instruments

    return sum(h.count for h in
               instruments.serving_phase_seconds().snapshot_values().values())


def wait_for_steps(n: int = 1, timeout: float = 120.0) -> float:
    """Return the time at which the ``n``-th engine phase from now ends
    (polled every millisecond; between phases the token count is still)."""
    start = steps_done()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if steps_done() - start >= n:
            return time.monotonic()
        time.sleep(0.001)
    raise harness.BenchmarkError(f"the engine did not finish {n} steps in "
                                 f"{timeout:.0f} s")


def check_reference(ctx, engine, samples: List[dict]) -> List[float]:
    """(b): teacher-forced through the reference, how far each generated
    token's logit sits below its position's maximum (the worst, a sample)."""
    import jax.numpy as jnp

    c = ctx.cell.config
    width = ctx.cell.mix["max_context"]
    worst = []
    for i, s in enumerate(samples):
        seq = s["prompt"] + s["tokens"]
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq          # causal: the padding sees, is not seen
        args = (engine.params, jnp.asarray(toks), c["n_head"],
                c["layer_norm_epsilon"])
        logits = np.asarray(
            ctx.first_call("reference_forward", reference.forward, *args)
            if i == 0 else reference.forward(*args))[0]
        n = len(s["prompt"])
        rows = logits[n - 1:n - 1 + len(s["tokens"])]
        picked = rows[np.arange(len(rows)), s["tokens"]]
        worst.append(float(np.max(rows.max(axis=-1) - picked)))
    return worst


def run(ctx: harness.Context) -> harness.Window:
    from horovod_tpu.serving import (ServingClient, ServingConfig,
                                     ServingFrontend)
    from horovod_tpu.serving.worker import ServingWorker, build_replica_engine

    cell, mix, c = ctx.cell, ctx.cell.mix, ctx.cell.config
    clients = mix["clients"]
    notes, ok = [], True

    def expect(cond, message):
        nonlocal ok
        if not cond:
            ok = False
            notes.append(f"CHECK FAILED: {message}")

    config = ServingConfig(max_context=mix["max_context"],
                           max_batch=mix.get("max_batch"))
    engine = ctx.first_call(
        "init_params", lambda: build_replica_engine(
            vocab_size=cell.vocab_rows, num_layers=c["n_layer"],
            num_heads=c["n_head"], d_model=c["n_embd"],
            max_seq_len=c["n_positions"], config=config, seed=ctx.seed))
    if ctx.trace:
        annotate(engine)
    import jax

    requests = traffic.request_list(ctx.seed + 1, mix["requests"], mix,
                                    c["vocab_size"])

    fe = ServingFrontend(secret="").start()
    worker = ServingWorker(fe.addr[0], fe.addr[1], engine, name="w0",
                           rank=1).start()
    cli = loop = None
    try:
        fe.wait_for_workers(1, timeout=60)
        cli = ServingClient(fe.addr[0], fe.addr[1], name="chipbench")
        # one throwaway request compiles (or loads) prefill and decode
        ctx.first_call("prefill_and_decode", lambda: cli.submit(
            requests[-1]["prompt"][:8], 2, request_id="warm").result(
            timeout=1100))
        # _prefill compiles two slices for every distinct prompt length:
        # warm each length the mix can draw, with a request of one token
        lengths = traffic.possible_lengths(mix["prompt_len"])
        ctx.first_call("prompt_length_slices", lambda: [
            f.result(timeout=600) for f in [
                cli.submit(requests[-1]["prompt"][:1] * n, 1,
                           request_id=f"warm-{n}") for n in lengths]])
        warm_compiles = ctx.compiles.count

        loop = ClosedLoop(cli, requests, clients, timeout=300)
        loop.start()
        deadline = time.monotonic() + 120
        while engine.stats()["active"] < min(clients, config.max_batch):
            if time.monotonic() > deadline or loop.errors:
                raise harness.BenchmarkError(
                    f"the closed loop did not fill: {loop.errors}")
            time.sleep(0.005)

        # ---- the window, cut on step completions
        t0 = wait_for_steps()
        setup_s = ctx.open_window()
        tokens0, counters0 = engine.stats()["tokens_generated"], counters()
        time.sleep(max(0.0, ctx.seconds - (time.monotonic() - t0)))
        t1 = wait_for_steps()
        tokens1, counters1 = engine.stats()["tokens_generated"], counters()
        compiled_inside = ctx.compiles.count - warm_compiles
        memory_peak = harness.memory_peak_bytes(jax.devices()[:1])
        window_s = t1 - t0
        with loop.lock:
            done = [d for d in loop.done if t0 <= d["done_t"] <= t1]
            errors = list(loop.errors)

        # ---- the traced slice: some engine steps of the same loop
        trace = None
        if ctx.trace:
            steps = mix["trace_steps"]
            with harness.profiler_slice():
                wait_for_steps(steps)
            trace = harness.trace_summary(steps, HOST_SPANS)
        loop.finish()

        # ---- correctness, outside the window
        expect(not errors, f"requests failed: {errors[:3]}")
        expect(compiled_inside == 0,
               f"{compiled_inside} compilations inside the window")
        expect(len(done) >= mix["check_requests"],
               f"only {len(done)} requests completed inside the window")
        wrong = [d["index"] for d in done if len(d["tokens"]) != d["asked"]]
        expect(not wrong, f"requests {wrong} have another length than asked")
        samples = sorted(done, key=lambda d: d["asked"])[:mix["check_requests"]]
        deadline = time.monotonic() + 60
        while engine.scheduler.has_work() and time.monotonic() < deadline:
            time.sleep(0.01)     # the cancelled requests leave the engine
        for s in samples:
            s["prompt"] = requests[s["index"] % len(requests)]["prompt"]
            alone = cli.submit(s["prompt"], s["asked"],
                               request_id=f"alone-{s['index']}").result(
                timeout=300)
            expect(alone == s["tokens"],
                   f"request r{s['index']}: decoded in the full batch "
                   f"{s['tokens']} but alone {alone}")
        below = check_reference(ctx, engine, samples) if samples else []
        notes.append(f"reference check: generated tokens' reference logits "
                     f"sit at most {below} below their position's maximum "
                     f"(tolerance {ARGMAX_LOGIT_TOL}); "
                     f"{len(samples)} requests alone == in the full batch")
        expect(all(b <= ARGMAX_LOGIT_TOL for b in below),
               f"a generated token's reference logit is {max(below, default=0)} "
               f"below the maximum")
    finally:
        if loop is not None:
            loop.stop.set()
        if cli is not None:
            cli.close()
        worker.stop()
        fe.stop()

    tokens_window = tokens1 - tokens0
    per_token_ms = [1e3 * d["latency_s"] / len(d["tokens"]) for d in done]
    client_tokens = sum(len(d["tokens"]) for d in done)
    notes.append(
        f"window {window_s:.2f} s: {tokens_window} tokens generated by the "
        f"engine, {client_tokens} in the {len(done)} requests clients "
        f"completed (the sample count of the per-token latency); "
        f"{len(errors)} failed")
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": tokens_window / window_s}
    if per_token_ms:
        end_to_end["serve_ms_per_token_p50"] = statistics.median(per_token_ms)
    return harness.Window(
        cell=cell, peak=ctx.peak, correct=ok,
        attempted=len(done) + len(errors), failed=len(errors),
        end_to_end=end_to_end,
        measured={"max_batch": config.max_batch,
                  "mean_client_latency_s": (statistics.fmean(
                      d["latency_s"] for d in done) if done else None)},
        counters=counters_delta(counters0, counters1),
        first_calls=ctx.first_calls, memory_peak_bytes=memory_peak,
        trace=trace,
        notes=notes)
