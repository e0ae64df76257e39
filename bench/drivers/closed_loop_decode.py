"""Driver: chat users on ``repro.serve.ServeEngine``, a closed loop.

``clients`` users each send a request, wait for its last token and send
the next one.  Requests come in the order of the traffic file's fixed
schedule of (prompt length, output length), from its start again when it
runs out; the seed draws the prompt tokens and the weights, so every seed
does the same work.  Decoding is
greedy.  The engine, its compiled step and its cache are built and warmed
in set-up (two rounds of short requests through every slot), and that same
engine serves the window.

Metrics, on the host clock after each ``step()`` (which returns once the
step's logits are on the host):

* ``decode_tokens_per_s``: output tokens completed in the window over the
  window's length;
* ``tpot_p95_ms``: 95th percentile of the time per output token over
  blocks of ``tpot_block`` consecutive output tokens of a request, all
  inside the window (a block spans some 250 ms or more, so the host
  clock's jitter stays small beside it);
* ``ttft_p50_ms``: median, over the requests sent in the window, of the
  time from the send to the first output token.  After the window closes
  no request is sent, and the engine steps on until every request sent
  in the window has its first token.

``correct``: once the window has closed and the engine's cache is freed,
the longest finished request and two more drawn from the seed are run
through the configuration's float32 reference with their served tokens;
the widest gap by which a served token's reference logit lies below the
reference's best must stay under the traffic file's limit.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from harness import costs, spec, trace


def arch_config(cfg: dict):
    """The program's configuration object for the sizes in ``cfg``."""
    from repro.configs import ArchConfig
    s = costs.sizes(cfg)
    return ArchConfig(
        name=cfg["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ff"], vocab_size=s["vocab"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], mlp_kind="swiglu",
        norm_kind="rms", dtype=cfg["torch_dtype"])


def _check_layout(arch, params):
    """The benchmark's weights must have the program's pytree layout."""
    import jax
    from repro.models import get_model
    want = jax.eval_shape(get_model(arch).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter layout differs from "
                           "the benchmark's weights")


class Requests:
    """The n-th request of the run: the schedule's n-th entry (cycling),
    with prompt tokens drawn from the seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.schedule = traffic["schedule"]
        self.vocab, self.seed = vocab, seed
        self.made: dict = {}

    def __getitem__(self, n: int):
        if n not in self.made:
            from repro.serve import Request
            p, o = self.schedule[n % len(self.schedule)]
            rng = np.random.default_rng([self.seed, 0, n])
            self.made[n] = Request(prompt=rng.integers(1, self.vocab,
                                                       p).tolist(),
                                   max_new_tokens=o)
        return self.made[n]


def _warm(engine, rounds: int = 2):
    """Short requests through every slot, twice: compiles the decode step
    and the slot reset for fresh and for served caches alike."""
    from repro.serve import Request
    for _ in range(rounds):
        for _ in range(engine.slots):
            if not engine.submit(Request(prompt=[1, 2], max_new_tokens=2)):
                raise RuntimeError("warm-up request found no free slot")
        while any(r is not None for r in engine.active):
            engine.step()


def run(ctx) -> dict:
    import jax

    from repro.serve import ServeEngine

    cfg, t = ctx.cell.config, ctx.cell.traffic
    ref = spec.config_reference(ctx.cell)
    arch = arch_config(cfg)
    slots, max_len = t["clients"], cfg["max_position_embeddings"]
    with ctx.spans.span("setup.weights"):
        params = jax.block_until_ready(ref.make_weights(cfg, ctx.seed))
    _check_layout(arch, params)
    reqs = Requests(t, cfg["vocab_size"], ctx.seed)
    with ctx.spans.span("setup.warmup"):
        engine = ServeEngine(arch, params, slots=slots, max_len=max_len)
        _warm(engine)
    ctx.mark_setup_done()

    traced_steps = int(t.get("trace_steps", 0)) if ctx.trace else 0
    send_t: dict[int, int] = {}
    tok_t: dict[int, list] = {}
    in_flight: list = []
    nxt = 0
    steps = traced_fed = 0
    c0 = ctx.compiles.count

    def send(now):
        nonlocal nxt
        req = reqs[nxt]
        if not engine.submit(req):
            raise RuntimeError("a client found no free slot")
        send_t[nxt], tok_t[nxt] = now, []
        in_flight.append(nxt)
        nxt += 1

    t0 = time.perf_counter_ns()
    for _ in range(slots):
        send(t0)
    t_end = None
    with contextlib.ExitStack() as stack:
        if traced_steps:
            stack.enter_context(trace.capture(ctx.trace_dir))
            stack.enter_context(ctx.spans.span("window"))
        while True:
            active = sum(r is not None for r in engine.active)
            with ctx.spans.span("serve.step"):
                emitted = engine.step()
            now = time.perf_counter_ns()
            ids = {id(r): i for i, r in ((i, reqs[i]) for i in in_flight)}
            for req, _tok in emitted:
                tok_t[ids[id(req)]].append(now)
            if t_end is None:
                steps += 1
                if steps <= traced_steps:
                    traced_fed += active
                if steps == traced_steps:
                    stack.close()
                done = [i for i in in_flight if reqs[i].done]
                for i in done:
                    in_flight.remove(i)
                if now - t0 >= ctx.seconds * 1e9:
                    t_end = now
                else:
                    with ctx.spans.span("serve.admit"):
                        for _ in done:
                            send(now)
            if t_end is not None and all(tok_t[i] for i in send_t):
                break
    window_compiles = ctx.compiles.count - c0
    window_s = (t_end - t0) / 1e9
    out_tokens = sum(sum(1 for x in ts if x <= t_end)
                     for ts in tok_t.values())
    k = t["tpot_block"]
    blocks = [(ts[i + k] - ts[i]) / k / 1e6
              for ts in ([x for x in v if x <= t_end] for v in tok_t.values())
              for i in range(0, len(ts) - k, k)]
    ttft = [(tok_t[i][0] - send_t[i]) / 1e6 for i in send_t]
    ctx.log(f"window: {window_s:.3f} s, {steps} steps, {len(send_t)} "
            f"requests sent, {out_tokens} output tokens, {len(blocks)} "
            f"{k}-token blocks, {window_compiles} compiles inside")
    peak = ctx.memory_peak()

    finished = [i for i in send_t if reqs[i].done]
    checks = check(ctx, ref, params, engine, reqs, finished, max_len,
                   control=ctx.control)
    checks.append(("window_compiles", window_compiles, 0))
    return dict(
        attempted=len(send_t),
        failed=sum(reqs[i].status not in ("running", "done")
                   for i in send_t),
        memory_peak_bytes=peak,
        end_to_end=dict(
            decode_tokens_per_s=out_tokens / window_s,
            tpot_p95_ms=float(np.percentile(blocks, 95)),
            ttft_p50_ms=float(np.percentile(ttft, 50))),
        counts=dict(steps=steps, traced_steps=min(steps, traced_steps),
                    traced_tokens_fed=traced_fed, slots=slots,
                    max_len=max_len),
        checks=checks)


def sample(finished, reqs, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` more drawn from the
    seed."""
    size = lambda i: len(reqs[i].prompt) + len(reqs[i].out)
    longest = max(finished, key=size)
    rest = [i for i in finished if i != longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(k)] for k in pick]


def check(ctx, ref, params, engine, reqs, finished, max_len,
          control: bool = False) -> list:
    t = ctx.cell.traffic
    if not finished:
        return [("unchecked_cell", 1, 0)]
    picked = sample(finished, reqs, ctx.seed, t["check_requests"])
    engine.cache = None                      # free the program's state
    gc.collect()
    t0 = time.perf_counter()
    res = ref.served_gaps(params, ctx.cell.config,
                          [(reqs[i].prompt, reqs[i].out) for i in picked],
                          pad_to=max_len, control=control)
    gap = max(float(g.max()) for g in res["gap"])
    served = sum(len(g) for g in res["gap"])
    short = sum(len(reqs[i].out) != reqs[i].max_new_tokens for i in picked)
    ctx.log(f"reference: {len(picked)} requests {picked}, {served} served "
            f"tokens, {time.perf_counter() - t0:.3f} s")
    ctx.details.update(checked_requests=picked, served_tokens=served)
    if control:
        # The control, the reference in fp8, stands in the program's place.
        ctx.details["program_gap"] = gap
        gap = ctx.details["control_gap"] = max(
            float(g.max()) for g in res["control_gap"])
    return [("served_token_gap", gap, t["limits"]["served_token_gap"]),
            ("short_requests", short, 0)]
