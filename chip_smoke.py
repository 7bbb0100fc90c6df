#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, in ONE
process that holds the chip (nothing here starts a child):

  device  `jax.devices()`: the platform must be `tpu`, else exit non-zero.
  train   ResNet-50 (224x224, 1000 classes, `mixed_bfloat16`, Nesterov) through
          `ComputationGraph.fit` on a `DeviceCacheDataSetIterator`.
  sparse  two layers of `zoo.sparse_moe_lm` at Keye-VL-2.0-30B-A3B's widths
          (4,096 tokens, top-2,048 indexer, 16 of 128 experts held) take
          train steps through `ComputationGraph.fit` on integer ids.
  serve   `transformer_lm` (d_model 512 x 4 blocks, vocab 8192, T 1024) behind
          `InferenceServer(warmup=True, kv_cache="paged", decode_slots=8)`,
          driven over HTTP from a thread of this process.
  cache   what this run's compiles hit and missed (nothing new is started).

Each phase prints one JSON line as it finishes; a failure in any phase raises
out of the script (exit code 1) at once. The last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and `"ok": true` never stands beside a platform other than `tpu`.

  --tiny      shrink every shape; the only way the script runs off-chip (the
              builder's CPU rehearsal; its last line says `"ok": false`).
  --chips 4   run ONLY the four-chip phase and what it is compared with:
              tensor-parallel serving, data-parallel fit, and an AOT-store
              round trip with four devices present.
  --seed N    weights and data are random, made from this seed.

One run of a smoke is not a benchmark: the seconds it prints separate set-up
and compile from steps and requests so that a reader can see where a cold run
spends its time, and nothing more.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

_HERE = os.path.dirname(os.path.abspath(__file__))
if not os.path.isdir(os.path.join(_HERE, "deeplearning4j_tpu")):
    sys.exit("chip_smoke.py runs from the root of a checkout: there is no "
             f"deeplearning4j_tpu/ beside {__file__}")
sys.path.insert(0, _HERE)

import numpy as np  # noqa: E402


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Sizes:
    """The shapes of one run: the repo's benchmarked widths, or `--tiny`."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        if tiny:
            self.resnet = dict(n_classes=10, image=32)
            self.batch, self.epochs = 8, 3
            self.lm = dict(vocab_size=64, t=32, d_model=64, n_heads=4,
                           n_blocks=1, decode_cache_length=64)
            self.sparse_lm = dict(
                vocab_size=48, t=64, d_model=64, n_blocks=2, n_heads=4,
                n_kv_heads=2, head_dim=16, n_experts=8, top_k=2,
                expert_hidden=32, experts_held=(0, 2), index_top_k=16,
                index_n_heads=2, index_head_dim=8)
            self.slots, self.page = 2, 16
            self.prompt_buckets = (8, 32)
            # Like the real ones, these span one to three KV pages.
            self.prompts = ((5, 4), (20, 5), (3, 6), (40, 3))
        else:
            self.resnet = dict(n_classes=1000, image=224)
            self.batch, self.epochs = 256, 3
            self.lm = dict(vocab_size=8192, t=1024, d_model=512, n_heads=8,
                           n_blocks=4, decode_cache_length=1024)
            # Keye-VL-2.0-30B-A3B's widths, two layers, 4,096 tokens: the
            # indexer selects (4,096 > 2,048), 16 of 128 experts are held.
            self.sparse_lm = dict(
                vocab_size=18992, t=4096, d_model=2048, n_blocks=2,
                n_heads=32, n_kv_heads=4, head_dim=128, n_experts=128,
                top_k=8, expert_hidden=768, experts_held=(0, 16),
                index_top_k=2048, index_n_heads=16, index_head_dim=64)
            self.slots, self.page = 8, 64
            self.prompt_buckets = (16, 64, 256)
            # (prompt length, tokens to generate): mixed, one per bucket
            # and one that lands in a bucket already used.
            self.prompts = ((5, 12), (23, 10), (100, 8), (200, 12))


# ------------------------------------------------------------------ metrics


def counter_total(name: str, **labels) -> float:
    from deeplearning4j_tpu import observability as obs

    fam = obs.metrics.get_family(name)
    if fam is None:
        return 0.0
    return sum(c.get() for c in fam.children()
               if all(c.labels.get(k) == v for k, v in labels.items()))


def cache_counts() -> dict:
    out = {}
    for source in ("persistent", "aot"):
        out[source] = {
            "hits": counter_total("dl4j_compile_cache_hits_total",
                                  source=source),
            "misses": counter_total("dl4j_compile_cache_misses_total",
                                    source=source)}
    out["xla_compiles"] = counter_total("dl4j_xla_compiles_total")
    out["compile_seconds"] = round(
        counter_total("dl4j_xla_compile_seconds_total"), 2)
    return out


def resolutions(*kernels) -> list:
    """What the registry resolved at real call signatures, grouped."""
    from deeplearning4j_tpu.kernels import registry

    groups: dict = {}
    for res in registry.resolved():
        if res.kernel in kernels:
            key = (res.kernel, res.impl, res.reason)
            groups[key] = groups.get(key, 0) + 1
    return [{"kernel": k, "impl": i, "signatures": n, "reason": r}
            for (k, i, r), n in sorted(groups.items())]


def hbm(device) -> dict:
    """Device memory as the backend reports it (the CPU backend does not).
    The peak is the process's so far: it never falls between phases."""
    stats = device.memory_stats() or {}
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use_so_far": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}


# ------------------------------------------------------------------- device


def phase_device(args):
    import jax
    import jaxlib

    from deeplearning4j_tpu import compilation
    from deeplearning4j_tpu import observability as obs

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" and not args.tiny:
        sys.exit(f"chip_smoke.py needs a TPU and jax found {dev}; there is "
                 "no CPU fallback (--tiny rehearses the control flow "
                 "off-chip)")
    if args.chips > dev["count"]:
        sys.exit(f"--chips {args.chips} needs {args.chips} devices; jax "
                 f"found {dev}")
    obs.install_jax_compile_hook()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # not a package on every image; the version is a label
        libtpu = None
    emit("device", **dev, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, compile_cache_root=compilation.cache_root(),
         jax_compilation_cache_dir=jax.config.jax_compilation_cache_dir,
         cache_dir_from_environment=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    return dev


# -------------------------------------------------------------------- train


def under_each_impl(kernel: str, fn):
    """`fn()` through the kernel's seam under every implementation the
    registry can be told to use (the per-kernel knob the parity tests
    use), as `{impl: result}` — only the impls that really resolved."""
    from deeplearning4j_tpu.kernels import registry

    knob = "DL4J_TPU_KERNEL_" + kernel.upper()
    out = {}
    for impl in ("pallas", "xla"):
        os.environ[knob] = impl
        registry.clear_cache()
        try:
            n0 = len(registry.resolved())
            value = fn()
            took = {r.impl for r in registry.resolved()[n0:]
                    if r.kernel == kernel}
            if took == {impl}:
                out[impl] = value
        finally:
            del os.environ[knob]
            registry.clear_cache()
    return out


def max_rel_diff(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def train_kernel_parity(seed: int) -> dict:
    """The train path's two Pallas bodies against their XLA references on
    a small input, on this device, through the layers' own seams: the
    interpret-mode parity tests say nothing about what Mosaic compiles.
    `fused_update` is in the step under `auto` for small leaves, as these
    are; `norm_act`'s BatchNorm body only when forced, which is how
    `under_each_impl` drives it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels import fused_update, norm_act

    rng = np.random.RandomState(seed + 4)
    x = jnp.asarray(rng.randn(4, 16, 16, 256), jnp.bfloat16)
    mean, var = jnp.mean(x, (0, 1, 2)), jnp.var(x, (0, 1, 2))
    gamma = jnp.asarray(rng.rand(256) + 0.5, jnp.bfloat16)
    beta = jnp.asarray(rng.randn(256), jnp.bfloat16)
    bn = under_each_impl("norm_act", lambda: np.asarray(jax.jit(
        lambda *a: norm_act.batchnorm_norm_act(*a, 1e-5, "relu"))(
            x, mean, var, gamma, beta), np.float32))
    tree = {"W": jnp.asarray(rng.randn(300, 257), jnp.float32),
            "b": jnp.asarray(rng.randn(257), jnp.float32)}
    vel = jax.tree_util.tree_map(lambda a: 0.1 * a, tree)

    def update():
        state, deltas = jax.jit(lambda v, g: fused_update.dispatch(
            "nesterovs", {"v": v}, g, 0.01, 3, (0.9,)))(vel, tree)
        return np.concatenate([np.ravel(l) for l in jax.tree_util.tree_leaves(
            (state, deltas))])

    upd = under_each_impl("fused_update", update)
    report = {}
    for name, got, tol in (("norm_act", bn, 2e-2), ("fused_update", upd,
                                                    1e-5)):
        report[name] = {"ran": sorted(got)}
        if len(got) == 2:
            rel = max_rel_diff(got["pallas"], got["xla"])
            check(np.all(np.isfinite(got["pallas"])) and rel < tol,
                  f"{name}: pallas differs from xla by {rel:.3e} of the "
                  f"peak (tolerance {tol})")
            report[name]["pallas_vs_xla_max_rel_diff"] = rel
    return report


def phase_train(args, size: Sizes):
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator)
    from deeplearning4j_tpu.datasets.staging import transfer_cast
    from deeplearning4j_tpu.kernels import fused_update, registry
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability import estimate_step_cost

    t_setup = time.perf_counter()
    # The zoo's lr (0.1) is the first rung of a schedule with a warm-up
    # this script has not; without one, random labels at random init blow
    # up in two steps. 1e-4 takes stable Nesterov steps at either size.
    conf = resnet50(dtype="bfloat16", lr=1e-4, **size.resnet)
    conf.global_conf.dtype_policy = {"name": "mixed_bfloat16",
                                     "transfer_dtype": "bfloat16"}
    net = ComputationGraph(conf).init()
    rng = np.random.RandomState(args.seed)
    image, classes = size.resnet["image"], size.resnet["n_classes"]
    batches = [DataSet(
        rng.rand(size.batch, image, image, 3).astype("float32"),
        np.eye(classes, dtype="float32")[rng.randint(0, classes, size.batch)])
        for _ in range(2)]
    it = DeviceCacheDataSetIterator(
        batches, transfer_dtype=net.dtype_policy.transfer_dtype)
    leaves0 = [np.asarray(l) for l in
               jax.tree_util.tree_leaves(net.params_tree)[:4]]
    setup_s = time.perf_counter() - t_setup

    # Warm-up epoch: stages the cache on the device, compiles, takes the
    # first two steps.
    t0 = time.perf_counter()
    net.fit(it)
    losses = [float(net.score_value)]
    first_fit_s = time.perf_counter() - t0

    # Does block_until_ready wait? Dispatch the timed epochs, then wait on
    # the parameters; a loss fetched after that must cost nothing more.
    epoch_s = []
    for _ in range(size.epochs):
        t0 = time.perf_counter()
        net.fit(it)
        jax.block_until_ready(net.params_tree)
        t_blocked = time.perf_counter()
        losses.append(float(net.score_value))
        epoch_s.append({"fit_and_block": round(t_blocked - t0, 4),
                        "loss_fetch_after_block": round(
                            time.perf_counter() - t_blocked, 4)})
    steps = size.epochs * len(batches)

    check(all(np.isfinite(l) for l in losses), f"loss not finite: {losses}")
    check(losses[-1] <= losses[0] * 1.05,
          f"loss rose over {steps} steps on two repeated batches: {losses}")
    leaves1 = [np.asarray(l) for l in
               jax.tree_util.tree_leaves(net.params_tree)[:4]]
    check(any(not np.array_equal(a, b) for a, b in zip(leaves0, leaves1)),
          "parameters did not change")
    check(all(np.all(np.isfinite(l)) for l in leaves1),
          "parameters not finite")

    memory = hbm(jax.devices()[0])

    # Which implementation did the step's kernels resolve to, and does the
    # compiled step agree, kernel by kernel? A Pallas body is a
    # `tpu_custom_call` named after its `pallas_call(name=...)` in the
    # compiled program; off-chip it is interpreted, and there is none.
    # Under `auto` on the chip: no `norm_act` call (every BatchNorm resolves
    # to `xla`, with the reason in its row; 46 before PR 25) and one
    # `fused_update` call for each of the step's 107 updater dispatches
    # whose leaves are all under a grid block, 72 of them (BatchNorm pairs,
    # small 1x1 convolutions); the 35 with a larger leaf resolve to `xla`
    # with the ravel for a reason (107 before PR 29; all 107 with
    # `DL4J_TPU_KERNEL_FUSED_UPDATE=pallas`, 0 under `DL4J_TPU_KERNELS=xla`).
    # A step loaded from an AOT store written under other rules would show
    # here as a kernel's calls without its `pallas` row. The registry
    # resolves while a program is traced, and a step that came from the
    # AOT store was not traced here: the cost estimate lowers the same step
    # again (a persistent-cache hit), which asks the registry.
    cost = estimate_step_cost(net, transfer_cast(
        MultiDataSet.from_dataset(batches[0]),
        net.dtype_policy.transfer_dtype))
    check(cost["flops"] and cost["bytes"], f"no cost analysis: {cost}")
    rows = resolutions("norm_act", "fused_update")
    check(rows, "the train step resolved no kernel through the registry")
    program = net._get_jit("train_step")
    texts = [e.as_text() for e in program.executables()
             if hasattr(e, "as_text")]
    check(texts, "the train step left no compiled executable to inspect "
                 "(is DL4J_TPU_COMPILE_CACHE=off?)")
    custom_calls = sum(t.count("tpu_custom_call") for t in texts)
    calls_by_kernel = {}
    for kernel in ("norm_act", "fused_update"):
        calls = calls_by_kernel[kernel] = sum(
            1 for t in texts for line in t.splitlines()
            if "tpu_custom_call" in line and kernel in line)
        says_pallas = any(r["kernel"] == kernel and r["impl"] == "pallas"
                          for r in rows)
        if jax.devices()[0].platform == "tpu":
            check((calls > 0) == says_pallas,
                  f"registry says {kernel} pallas={says_pallas} but the "
                  f"compiled step holds {calls} tpu_custom_call(s) of that "
                  f"name: {rows}")
    check(sum(calls_by_kernel.values()) == custom_calls,
          f"{custom_calls} tpu_custom_call(s) in the step, of which "
          f"{calls_by_kernel} carry a registry kernel's name")
    # One updater dispatch a layer with parameters; the rule counts those
    # whose largest leaf is under `fused_update`'s limit.
    dispatches = [[l.size for l in jax.tree_util.tree_leaves(state)]
                  for state in net.opt_state.values()]
    dispatches = [sizes for sizes in dispatches if sizes]
    small = sum(max(sizes) < fused_update._RAVEL_LIMIT
                for sizes in dispatches)
    if jax.devices()[0].platform == "tpu":
        want = {"auto": small, "pallas": len(dispatches), "xla": 0}[
            registry.mode_for("fused_update")[0]]
        check(calls_by_kernel["fused_update"] == want,
              f"{calls_by_kernel['fused_update']} fused_update call(s) in "
              f"the step, the rule gives {want} ({small} of "
              f"{len(dispatches)} dispatches hold small leaves only)")
    emit("train", model=f"resnet50 {image}x{image}x{classes}",
         batch=size.batch, policy="mixed_bfloat16",
         steps_after_warmup=steps, losses=[round(l, 5) for l in losses],
         setup_seconds=round(setup_s, 2),
         first_fit_seconds_with_compile=round(first_fit_s, 2),
         epochs=epoch_s, steps_per_epoch=len(batches),
         hbm=memory, step_flops=cost["flops"],
         step_bytes_accessed=cost["bytes"], kernels=rows,
         tpu_custom_calls_in_step=custom_calls,
         tpu_custom_calls_by_kernel=calls_by_kernel,
         updater_dispatches={"all": len(dispatches), "small_leaves": small},
         kernel_parity=train_kernel_parity(args.seed))


# ---------------------------------------------------------------- sparse lm


def phase_sparse_lm(args, size: Sizes):
    """Two layers of `zoo.sparse_moe_lm` (grouped-query attention under the
    indexer's selection, dropless routed experts of which a share is held)
    take train steps through `ComputationGraph.fit` on integer ids."""
    import jax

    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        DeviceCacheDataSetIterator)
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    kw = dict(size.sparse_lm)
    vocab, t = kw.pop("vocab_size"), kw["t"]
    net = ComputationGraph(zoo.sparse_moe_lm(
        vocab, seed=args.seed, dtype_policy={
            "name": "mixed_bfloat16", "transfer_dtype": "bfloat16"},
        **kw)).init()
    ids = np.random.RandomState(args.seed).randint(
        0, vocab, (1, t + 1)).astype(np.int32)
    data = DeviceCacheDataSetIterator(
        [DataSet(ids[:, :-1], ids[:, 1:], None,
                 np.full((1, t), 1.0 / t, np.float32))],
        transfer_dtype=net.dtype_policy.transfer_dtype)
    frozen = np.asarray(net.params_tree["attn0"]["Wiq"])
    t0 = time.perf_counter()
    net.fit(data)
    losses = [net.score_value]
    first_fit_s = time.perf_counter() - t0
    with CompileNames() as compiled:
        t0 = time.perf_counter()
        for _ in range(3):
            net.fit(data)
        losses.append(net.score_value)
        steps_s = time.perf_counter() - t0
    check(not compiled.names, f"compiled after warm-up: {compiled.names}")
    check(all(np.isfinite(l) for l in losses) and losses[-1] < losses[0],
          f"sparse lm loss did not fall: {losses}")
    check(np.array_equal(np.asarray(net.params_tree["attn0"]["Wiq"]), frozen),
          "the indexer's frozen weights changed")
    gauges = {
        name: {c.labels["layer"]: round(c.get(), 4)
               for c in obs.metrics.get_family(name).children()}
        for name in ("dl4j_dsa_selected_keys_mean",
                     "dl4j_moe_pairs_held_share",
                     "dl4j_moe_expert_load_max_over_mean")}
    top = kw["index_top_k"]
    want = float(np.minimum(np.arange(t) + 1, top).mean())
    check(all(abs(v - want) < 1e-3 * want
              for v in gauges["dl4j_dsa_selected_keys_mean"].values()),
          f"selected keys a query {gauges} != {want}")
    emit("sparse_lm", model=f"sparse_moe_lm d{kw['d_model']}x{kw['n_blocks']}"
         f" T{t} experts {kw['experts_held'][1]}/{kw['n_experts']}",
         losses=[round(l, 5) for l in losses],
         first_fit_seconds_with_compile=round(first_fit_s, 2),
         seconds_per_step_after=round(steps_s / 3, 4), gauges=gauges,
         kernels=resolutions("masked_attention", "grouped_matmul"),
         hbm=hbm(jax.devices()[0]))


# -------------------------------------------------------------------- serve


def http_json(url: str, payload=None, timeout: float = 600.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body = r.read()
    return body.decode() if url.endswith("/metrics") else json.loads(body)


def scrape_total(scrape: str, family: str) -> float:
    return sum(float(line.rsplit(" ", 1)[1]) for line in scrape.splitlines()
               if line.startswith(family) and not line.startswith("#")
               and line[len(family):len(family) + 1] in ("{", " "))


class CompileNames(logging.Handler):
    """Names of what jax compiles while installed (`jax_log_compiles`), so
    that a compile after warm-up is reported by name, not as a count."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[len("Compiling "):].split(" with ")[0])

    def __enter__(self):
        import jax

        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        import jax

        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", False)


# The HTTP clients: threads of this process, never a child. Every call's
# future is read, so a failed request raises here.
CLIENTS = ThreadPoolExecutor(max_workers=4, thread_name_prefix="smoke-client")


def client(url: str, payload=None):
    return CLIENTS.submit(http_json, url, payload).result()


def build_lm(size: Sizes, seed: int):
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    return ComputationGraph(zoo.transformer_lm(seed=seed, **size.lm)).init()


def make_prompts(size: Sizes, seed: int):
    rng = np.random.RandomState(seed + 1)
    v = size.lm["vocab_size"]
    return [([int(t) for t in rng.randint(1, v, n)], steps)
            for n, steps in size.prompts]


def lm_probs(lm, ids) -> np.ndarray:
    """`net.output` on an `[b, t]` id grid -> `[b, t, V]` probabilities (a
    ComputationGraph answers with a list, one array per output)."""
    return np.asarray(lm.output(np.asarray(ids, np.int32)[..., None])[0])


def greedy_agreement(lm, prompt, got, want):
    """Greedy ids against the reference's. A random-weight LM spreads its
    mass thinly, so two correct programs that round differently can part
    at a near-tie: where ids first differ, the served token must be within
    2% of the reference's best under the reference's own distribution, and
    what follows a legitimate fork is not compared. Returns "equal" or
    "near_tie"."""
    if list(got) == list(want):
        return "equal"
    check(len(got) == len(want), f"length {len(got)} != {len(want)}")
    i = next(k for k, (a, b) in enumerate(zip(got, want)) if a != b)
    check(i >= len(prompt), "the prompt itself came back changed")
    probs = lm_probs(lm, [want[:i]])[0, -1]
    check(probs[got[i]] >= 0.98 * probs[want[i]],
          f"ids differ at {i}: served {got[i]} (p={probs[got[i]]:.3e}), "
          f"reference {want[i]} (p={probs[want[i]]:.3e})")
    return "near_tie"


def paged_decode_under_each_impl(lm, size: Sizes, prompt):
    """One paged decode step under every implementation the registry
    offers here, same weights and prompt: next-token distributions
    compared (ROADMAP A0.2)."""
    import jax

    from deeplearning4j_tpu.kernels import registry
    from deeplearning4j_tpu.models.zoo import PagedDecodeStepper

    heads, d = size.lm["n_heads"], size.lm["d_model"] // size.lm["n_heads"]
    n_seq = size.lm["decode_cache_length"] // size.page
    sig = dict(shapes=(size.slots, 1, heads, d, size.slots * n_seq + 1,
                       size.page, n_seq),
               dtypes=("float32",),
               meta=(("causal", True),))
    auto = registry.resolve("flash_attention_paged", **sig)
    knob = "DL4J_TPU_KERNEL_FLASH_ATTENTION_PAGED"
    offered = {}
    for impl in ("pallas", "xla"):
        os.environ[knob] = impl
        registry.clear_cache()
        try:
            if registry.resolve("flash_attention_paged", **sig).impl != impl:
                continue  # not offered at this shape on this backend
            stepper = PagedDecodeStepper(lm, size.slots, page_size=size.page)
            probs, state, n = stepper.prefill(prompt,
                                              pad_to=size.prompt_buckets[-1])
            stepper.install(0, state, n)
            first = int(np.argmax(probs))
            offered[impl] = np.asarray(
                stepper.step([first] + [0] * (size.slots - 1)))[0]
        finally:
            del os.environ[knob]
            registry.clear_cache()
    check(auto.impl in offered, f"auto resolved {auto.impl!r}, not offered")
    report = {"auto": auto.impl, "auto_reason": auto.reason,
              "offered": sorted(offered)}
    if len(offered) == 2:
        a, b = offered["pallas"], offered["xla"]
        rel = max_rel_diff(a, b)
        check(np.all(np.isfinite(a)) and rel < 2e-2,
              f"paged decode: pallas vs xla differ by {rel:.3e} of the peak")
        report["pallas_vs_xla_max_rel_diff"] = rel
        report["argmax_equal"] = bool(np.argmax(a) == np.argmax(b))
    if jax.devices()[0].platform == "tpu":
        check("pallas" in offered or auto.impl == "xla",
              "no Pallas paged kernel offered on the chip")
    return report


def phase_serve(args, size: Sizes):
    import jax

    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.serving import InferenceServer

    gc.collect()  # the train phase's arrays are gone before this one counts
    hbm_at_start = hbm(jax.devices()[0])
    t0 = time.perf_counter()
    lm = build_lm(size, args.seed)
    window = size.lm["t"]
    server = InferenceServer(
        None, port=0, warmup=True, kv_cache="paged", decode_slots=size.slots,
        kv_page_size=size.page, max_batch_size=2,
        prompt_buckets=size.prompt_buckets)
    # lm=True: a model that cannot serve /generate is an error here, not a
    # server that quietly answers /predict only.
    server.add_model("default", net=lm, lm=True)
    server.start()
    try:
        check(server.wait_ready(timeout=900), "warm-up still running")
        warm_s = time.perf_counter() - t0
        url = server.url

        models = client(url + "/v1/models")["models"]
        row = next(m for m in models if m["name"] == "default")
        check(row["lm"] and row["status"] == "ready", f"/v1/models: {row}")
        compiles0 = scrape_total(client(url + "/metrics"),
                                 "dl4j_xla_compiles_total")

        rng = np.random.RandomState(args.seed + 2)
        v = size.lm["vocab_size"]
        xs = [rng.randint(1, v, (rows, window)) for rows in (1, 1, 2)]
        prompts = make_prompts(size, args.seed)
        with CompileNames() as compiled_under_traffic:
            t1 = time.perf_counter()
            preds = [np.asarray(client(url + "/predict", {
                "data": x.tolist()})["predictions"], np.float32) for x in xs]
            predict_s = time.perf_counter() - t1

            t1 = time.perf_counter()
            pending = [CLIENTS.submit(http_json, url + "/generate", {
                "prompt_ids": prompt, "n_steps": steps, "temperature": 0.0})
                for prompt, steps in prompts]  # all four in flight at once
            served = [f.result()["ids"] for f in pending]
            generate_s = time.perf_counter() - t1

        scrape = client(url + "/metrics")
        compiles1 = scrape_total(scrape, "dl4j_xla_compiles_total")
        check(compiles1 == compiles0 and not compiled_under_traffic.names,
              f"{compiles1 - compiles0} XLA compile(s) after warm-up: "
              f"{compiled_under_traffic.names}")
        build = [l for l in scrape.splitlines()
                 if l.startswith("dl4j_build_info{") and l.endswith(" 1")]
        platform = jax.devices()[0].platform
        check(build and f'backend="{platform}"' in build[0],
              f"dl4j_build_info does not say {platform}: {build}")
        memory = hbm(jax.devices()[0])
    finally:
        server.stop()

    # References, after the compile count is taken: they compile their own.
    for x, got in zip(xs, preds):
        want = lm_probs(lm, x)
        check(got.shape == want.shape, f"{got.shape} != {want.shape}")
        rel = max_rel_diff(got, want)
        check(np.all(np.isfinite(got)) and rel < 2e-2,
              f"/predict differs from net.output by {rel:.3e} of the peak")
    agree = [greedy_agreement(
        lm, prompt, got,
        zoo.generate_lm(lm, prompt, steps, window=window, use_cache=True,
                        temperature=0.0))
        for (prompt, steps), got in zip(prompts, served)]
    lm.rnn_clear_previous_state()
    paged = paged_decode_under_each_impl(lm, size, prompts[1][0])

    emit("serve", model="transformer_lm " + " ".join(
        f"{k}={v}" for k, v in size.lm.items()),
        decode_slots=size.slots, kv_page_size=size.page,
        warmup_seconds_with_compile=round(warm_s, 2),
        predict_requests=len(xs), predict_seconds=round(predict_s, 2),
        generate_requests=len(prompts),
        generate_seconds=round(generate_s, 2),
        generated_tokens=sum(s for _, s in prompts),
        ids_vs_generate_lm=agree, xla_compiles_after_warmup=0,
        build_info=build[0], hbm_at_start=hbm_at_start,
        hbm_under_traffic=memory, paged_decode=paged,
        kernels_traced_in_this_run=resolutions(
            "flash_attention", "flash_attention_paged", "norm_act"))


# --------------------------------------------------------------- four chips


def phase_four_chips(args, size: Sizes):
    import jax

    from deeplearning4j_tpu import (MultiLayerNetwork, compilation)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.parallel import mesh as mesh_mod
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.serving.host import per_chip_bytes

    n = args.chips
    devices = jax.devices()[:n]
    prompts = make_prompts(size, args.seed)[:2]

    # (a) tensor-parallel serving against the same LM unsharded on device 0.
    def serve(ways):
        lm = build_lm(size, args.seed)
        server = InferenceServer(
            None, port=0, warmup=True, kv_cache="paged",
            decode_slots=size.slots, kv_page_size=size.page,
            max_batch_size=1, prompt_buckets=size.prompt_buckets,
            model_parallel=ways)
        server.add_model("default", net=lm, lm=True)
        server.start()
        try:
            check(server.wait_ready(timeout=900), "warm-up still running")
            ids = [client(server.url + "/generate", {
                "prompt_ids": p, "n_steps": s, "temperature": 0.0})["ids"]
                for p, s in prompts]
            stepper = server.models.get("default").scheduler.stepper
            pages = {layer: {k: s[k] for k in ("k_pages", "v_pages")}
                     for layer, s in stepper._state.items()
                     if "k_pages" in s}
            placed = {
                "param_devices": sorted({d.id for leaf in
                                         jax.tree_util.tree_leaves(
                                             lm.params_tree)
                                         for d in leaf.sharding.device_set}),
                "page_devices": sorted({sh.device.id for leaf in
                                        jax.tree_util.tree_leaves(pages)
                                        for sh in leaf.addressable_shards}),
                "per_chip_bytes": (per_chip_bytes(lm.params_tree)
                                   + per_chip_bytes(pages)),
                "sharding": server.models.get("default").sharding}
        finally:
            server.stop()
        return lm, ids, placed

    t0 = time.perf_counter()
    lm1, ids1, one = serve(1)
    lmn, idsn, many = serve(n)
    agree = [greedy_agreement(lm1, p, got, want)
             for (p, _), got, want in zip(prompts, idsn, ids1)]
    ratio = many["per_chip_bytes"] / one["per_chip_bytes"]
    # At --tiny widths most leaves are under the sharding rules' size
    # floor and replicate; the gate is for the real model.
    check(size.tiny or ratio <= 0.35,
          f"per-chip bytes ratio {ratio:.3f} > 0.35")
    want_ids = sorted(d.id for d in devices)
    check(many["param_devices"] == want_ids
          and many["page_devices"] == want_ids,
          f"not on {n} distinct devices: {many}")
    check(one["param_devices"] == [devices[0].id], f"unsharded: {one}")
    rows = mesh_mod.describe_shardings(
        lmn, mesh_mod.create_mesh((1, n), ("data", "model")), "model")
    sharded_bytes = sum(r["bytes"] for r in rows if not r["replicated"])
    emit("four_chips.tensor_parallel_serving", ways=n,
         ids_vs_unsharded=agree, per_chip_bytes_ratio=round(ratio, 4),
         per_chip_bytes=many["per_chip_bytes"],
         unsharded_bytes=one["per_chip_bytes"], sharding=many["sharding"],
         param_devices=many["param_devices"],
         page_devices=many["page_devices"],
         sharded_param_share=round(
             sharded_bytes / sum(r["bytes"] for r in rows), 4),
         kernels=resolutions("flash_attention_paged"),
         seconds=round(time.perf_counter() - t0, 2))

    # (b) data-parallel LeNet against one device, same seed and batches.
    t0 = time.perf_counter()
    rng = np.random.RandomState(args.seed + 3)
    batch = 16 * n
    data = [DataSet(rng.rand(batch, 28, 28, 1).astype("float32"),
                    np.eye(10, dtype="float32")[rng.randint(0, 10, batch)])
            for _ in range(4)]
    single = MultiLayerNetwork(zoo.lenet_mnist()).init()
    wrapped = MultiLayerNetwork(zoo.lenet_mnist()).init()
    pw = ParallelWrapper(wrapped, mesh=mesh_mod.create_mesh(devices=devices))
    loss1, lossn = [], []
    for ds in data:
        single.fit(ds)
        loss1.append(float(single.score_value))
        pw.fit(ds)
        lossn.append(float(wrapped.score_value))
    check(np.allclose(loss1, lossn, rtol=2e-2, atol=1e-3),
          f"data-parallel loss {lossn} != one-device loss {loss1}")
    x = jax.device_put(np.asarray(data[0].features), mesh_mod.data_sharding(
        pw.mesh, 4, pw.data_axis))
    shard_devices = sorted(s.device.id for s in x.addressable_shards)
    check(shard_devices == want_ids
          and all(s.data.shape[0] == batch // n
                  for s in x.addressable_shards),
          f"batch shards: {shard_devices}")
    param_devices = sorted({d.id for leaf in
                            jax.tree_util.tree_leaves(wrapped.params_tree)
                            for d in leaf.sharding.device_set})
    check(param_devices == want_ids, f"replicas on {param_devices}")
    emit("four_chips.data_parallel_fit", devices=n, steps=len(data),
         loss_one_device=[round(l, 5) for l in loss1],
         loss_data_parallel=[round(l, 5) for l in lossn],
         batch_shard_devices=shard_devices,
         seconds=round(time.perf_counter() - t0, 2))

    # (c) AOT store: a one-device program, stored and loaded again while
    # four devices are present.
    ds = data[0]
    first = MultiLayerNetwork(zoo.lenet_mnist()).init()
    hits0 = counter_total("dl4j_compile_cache_hits_total", source="aot")
    summary = first.warmup(ds, kinds=["output"])
    want = np.asarray(first.output(ds.features))
    compilation.reset()  # drop this process's executables: load from disk
    again = MultiLayerNetwork(zoo.lenet_mnist()).init()
    got = np.asarray(again.output(ds.features))
    hits = counter_total("dl4j_compile_cache_hits_total", source="aot") - hits0
    check(hits >= 1, f"no AOT hit after the round trip (warmup: {summary})")
    check(np.allclose(got, want, rtol=1e-5, atol=1e-6),
          "AOT-loaded program disagrees with the compiled one")
    emit("four_chips.aot_store_round_trip", devices_present=len(jax.devices()),
         warmup=summary, aot_hits=hits,
         program_devices=sorted(
             d.id for d in jax.tree_util.tree_leaves(
                 again.params_tree)[0].sharding.device_set))


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="shrunk shapes; the only mode that runs off-chip")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    size = Sizes(args.tiny)

    t0 = time.perf_counter()
    dev = phase_device(args)
    if args.chips == 4:
        phase_four_chips(args, size)
    else:
        phase_train(args, size)
        phase_sparse_lm(args, size)
        phase_serve(args, size)
    emit("cache", **cache_counts(),
         total_seconds=round(time.perf_counter() - t0, 2))
    on_chip = dev["platform"] == "tpu"
    last = {"ok": on_chip, "device": dev}
    if not on_chip:
        last["rehearsal"] = "passed off-chip (--tiny); not a chip run"
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
