"""Driver `generate`: serve the configuration's LM behind `InferenceServer`
and offer it the cell's traffic over HTTP `/generate`.

Set-up: the network on the device, the server with the cell's settings
(`warmup=False`; only the generation scheduler is warmed, not the `/predict`
batcher, which no cell uses), the correctness check against the plain
reference, then `lead_in_s` seconds of the cell's own load so that the
window opens on a system in its steady state. The window then runs for
`--seconds`; what it counts are the requests that COMPLETED inside it, read
from the program's request ledger (exact, one clock) and joined to what
this process sent.
"""

from __future__ import annotations

import time


def _generate(conn, prompt, n_new: int):
    import json

    conn.request("POST", "/generate", body=json.dumps(
        {"prompt_ids": prompt, "n_steps": n_new, "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    if resp.status != 200:
        raise RuntimeError(f"HTTP {resp.status}: {body[:200]!r}")
    return json.loads(body)["ids"]


def _check_against_reference(cell, built, server, args) -> dict:
    """Seeded prompts, greedy, against the plain reference's argmax.

    The program's steppers hand token ids to the network as floats, and the
    dtype policy casts float inputs to its compute dtype: under bf16 compute
    an id above 256 reaches the embedding rounded to 8 bits of mantissa
    (PERF.md section 7, first row). So the check's prompts are drawn from
    ids that bf16 holds exactly, and the check first ASKS the program
    whether it rounds: a prompt and its copy with every id one higher
    (which bf16 rounds back) generate the same tokens only if it does. If
    it does, a served token is compared as long as everything before it is
    exact; the first generated token that is not ends the comparison, for
    the program conditions on another token from there. A program that
    keeps ids exact has all `new_tokens` compared."""
    import http.client

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import cells

    spec = cell.spec["reference_check"]
    ref = cells.load_module("reference", spec["reference"])
    rng = np.random.RandomState([args.seed % (2 ** 32), 0xC0DE])
    lo, hi = spec["prompt_tokens"]
    n_new = int(spec["new_tokens"])

    def exact(token: int) -> bool:
        return float(np.asarray(token, jnp.bfloat16)) == float(token)

    # Ids of 512 and more that bf16 holds exactly are at least 4 apart, so
    # id + 1 rounds back to id.
    candidates = np.arange(512, built["vocab"] - 1)
    ids_exact = candidates[candidates.astype(jnp.bfloat16).astype(np.int64)
                           == candidates]
    prompts = [[int(t) for t in rng.choice(ids_exact, rng.randint(lo, hi + 1))]
               for _ in range(int(spec["prompts"]))]
    conn = http.client.HTTPConnection(server.host, server.port, timeout=600)
    try:
        served = [_generate(conn, p, n_new) for p in prompts]
        shifted = _generate(conn, [t + 1 for t in prompts[0]], n_new)
    except RuntimeError as e:
        return {"ok": False, "verdicts": [str(e)]}
    finally:
        conn.close()
    rounds_ids = shifted[len(prompts[0]):] == served[0][len(prompts[0]):]

    # The reference, padded to one length so that it compiles once: causal,
    # so the rows before the padding do not see it.
    pad = hi + n_new
    params = built["net"].params_tree
    fwd = jax.jit(lambda p, ids: ref.forward(
        p, ids, built["n_heads"], built["n_blocks"]))

    def probs_of(ids):
        x = np.zeros((pad,), np.int32)
        x[:len(ids)] = ids
        return np.asarray(fwd(params, jnp.asarray(x))[len(ids) - 1])

    verdicts, worst, compared = [], 1.0, 0
    for p, ids in zip(prompts, served):
        verdict, ratio, n = ref.greedy_agreement(
            probs_of, p, ids, n_new, float(spec["near_tie_ratio"]),
            comparable=exact if rounds_ids else (lambda token: True))
        verdicts.append(verdict)
        worst, compared = min(worst, ratio), compared + n
    return {"ok": all(v in ("equal", "near_tie") for v in verdicts),
            "verdicts": verdicts, "worst_ratio": worst,
            "tokens_compared": compared,
            "program_rounds_token_ids": bool(rounds_ids)}


def run(cell, args, clock) -> dict:
    import jax

    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.serving import InferenceServer

    from benchmark.harness import (counters, device, load as load_mod, stats,
                                   trace_reduce, traffic as traffic_mod)

    built = cell.build(args.seed)
    clock.mark("model")
    net = built["net"]
    traffic = cell.spec["traffic"]
    srv = dict(cell.spec["server"])
    slots, page = int(srv["decode_slots"]), built["page_size"]
    pages = traffic_mod.pages_for(traffic, slots, page)
    if traffic_mod.longest_request(traffic) > built["capacity"]:
        raise ValueError("a request of this traffic exceeds the model's "
                         "positions")
    name = "lm"
    server = InferenceServer(
        None, port=0, warmup=False, kv_cache="paged", decode_slots=slots,
        kv_page_size=page, kv_pages=pages,
        prompt_buckets=tuple(srv["prompt_buckets"]),
        prefix_cache=bool(srv.get("prefix_cache", False)),
        generate_queue_depth=int(srv.get("generate_queue_depth", 256)),
        default_model=name)
    server.add_model(name, net=net, lm=True)
    scheduler = server.models.get(name).scheduler
    scheduler.warmup()
    clock.mark("scheduler_warmup")
    server.start()
    load = None
    try:
        check = _check_against_reference(cell, built, server, args)
        clock.mark("reference_check")

        stream = traffic_mod.Stream(traffic, args.seed, built["vocab"])
        readings = {
            "prefill_s": lambda: counters.counter_total(
                "dl4j_serving_dispatch_seconds_total", model=name,
                phase="prefill"),
            "decode_s": lambda: counters.counter_total(
                "dl4j_serving_dispatch_seconds_total", model=name,
                phase="decode"),
            "decode_steps": lambda: counters.histogram_sum_count(
                "dl4j_serving_decode_step_seconds", model=name),
            "xla_compiles": lambda: counters.counter_total(
                "dl4j_xla_compiles_total"),
        }
        tracer = trace_reduce.for_window(args, traffic)
        obs.request_ledger.clear()
        load = load_mod.Load(stream, server.host, server.port).start()
        lead_in = float(traffic.get("lead_in_s", 0.0))
        with counters.CompileNames() as compiled:
            time.sleep(lead_in)
            lead_compiles = list(compiled.names)
            del compiled.names[:]

            setup_s = clock.setup_done()
            before = counters.Snapshot(readings)
            t0 = time.perf_counter()
            while True:
                now = time.perf_counter() - t0
                tracer.tick(now)
                if now >= args.seconds:
                    break
                time.sleep(min(0.05, args.seconds - now))
            t1 = time.perf_counter()
            delta = counters.Snapshot(readings).delta(before)
            load.stop_sending()
            sent = load.snapshot()
            ledger = obs.request_ledger.snapshot()
        tracer.finish()
        window_s = t1 - t0
        executables = scheduler.stepper.cg._get_jit(
            "output", train=False, keep_rnn_state=True).executables()
        memory = device.memory_peak_bytes(
            cell.chips, device.program_footprint_bytes(executables))
    finally:
        # Stopping the server answers what is still open ("server stopped"),
        # so every client thread ends; none outlives this function.
        if load is not None:
            load.stop_sending()
        server.stop()
        if load is not None and not load.join():
            raise RuntimeError("a load thread did not end")

    completed, failed, attempted = load_mod.window_records(
        sent, ledger, t0, t1)
    problems = []
    if not check["ok"]:
        problems.append(f"reference check: {check['verdicts']}")
    if compiled.names or delta["xla_compiles"]:
        problems.append(f"compiled inside the window: {compiled.names} "
                        f"({delta['xla_compiles']} XLA compiles)")
    if len(completed) < 2:
        problems.append(f"{len(completed)} requests completed in the window")
        summary = {"out_tokens_per_s": 0.0, "ttft_p90_ms": 0.0,
                   "tpot_p90_ms": 0.0, "completed": len(completed),
                   "out_tokens": 0}
    else:
        summary = stats.serving_summary(completed, window_s)
    lateness = [r["t_send"] - r["due"] for r in sent if r["due"] is not None]
    return {
        "setup_s": setup_s,
        "window_s": window_s,
        "correct": not problems,
        "problems": problems,
        "attempted": attempted,
        "failed": failed,
        "end_to_end": {k: summary[k] for k in
                       ("out_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms")},
        "memory_peak_bytes": memory,
        "info": {"completed": summary["completed"],
                 "out_tokens": summary["out_tokens"],
                 "sent": len(sent), "reference_check": check,
                 "compiled_in_lead_in": lead_compiles,
                 "kv_pages": pages,
                 "generator_late_p90_s": (stats.percentile(lateness, 90)
                                          if lateness else None)},
        "context": {
            "cell": cell, "built": built, "delta": delta,
            "window_s": window_s, "completed": completed, "slots": slots,
            "tracer": tracer, "executables": executables,
        },
    }
