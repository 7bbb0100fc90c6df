"""Generation fast path (ISSUE 15): paged/shared KV cache, prefix cache,
speculative decoding.

Acceptance coverage:

- paged-vs-dense BIT-IDENTITY through the scheduler, greedy and seeded
  (same per-request draw order as sequential `generate_lm`);
- copy-on-write divergence after a shared prefix: two slots sharing one
  tail page append different tokens and each matches its own dense
  reference, with the pool's shared/used counts moving through the CoW;
- prefix cache: a repeat prompt skips prefill (hit counter, identical
  output), entries hold pool refs, eviction reclaims pages;
- page-leak check: the pool's free count returns to baseline after slot
  recycling, deadline expiry, and prefix-cache clear;
- speculative exactness gate: greedy decode through the draft-model
  scheduler is bit-identical to the non-speculative scheduler and to
  `generate_lm`; an identical-weights draft actually accepts tokens;
- the new metric families ride one `/metrics` scrape.
"""

import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.models.kv_pool import (
    KVPagePool,
    PoolExhaustedError,
    PrefixCache,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.serving import InferenceServer
from deeplearning4j_tpu.serving.scheduler import GenerationScheduler

V = 17
CAP = 32
PAGE = 8


def _lm(d_model=16, seed=12345):
    conf = zoo.transformer_lm(vocab_size=V, t=16, d_model=d_model,
                              n_heads=2, n_blocks=1,
                              decode_cache_length=CAP, seed=seed)
    return ComputationGraph(conf).init()


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def lm_twin():
    # Same config + seed as `lm`: identical weights, so as a draft its
    # argmax always agrees with the target (accept rate 1).
    return _lm()


@pytest.fixture(scope="module")
def draft_lm():
    return _lm(d_model=8, seed=999)


def _ref(lm, prompt, n, **kw):
    return zoo.generate_lm(lm, prompt, n, window=16, use_cache=True, **kw)


# ------------------------------------------------------------------ pool


class TestKVPagePool:
    def test_geometry_and_zero_page(self):
        pool = KVPagePool(slots=2, capacity=32, page_size=8)
        assert pool.pages_per_seq == 4
        assert pool.num_pages == 2 * 4 + 1
        assert pool.free_count == pool.num_pages - 1  # page 0 reserved
        with pytest.raises(ValueError):
            KVPagePool(slots=2, capacity=30, page_size=8)
        with pytest.raises(ValueError):
            pool.ref([0])

    def test_install_free_and_counts(self):
        pool = KVPagePool(slots=2, capacity=32, page_size=8)
        pages = pool.install_slot(0, 10)  # ceil(10/8) = 2 pages
        assert len(pages) == 2
        assert list(pool.table[0, :2]) == pages
        assert pool.counts() == {"free": 6, "used": 2, "shared": 0}
        pool.free_slot(0)
        assert pool.counts()["free"] == 8
        assert not pool.table.any()

    def test_shared_install_and_cow_plan(self):
        pool = KVPagePool(slots=2, capacity=32, page_size=8)
        pages = pool.install_slot(0, 5)      # one partially-filled page
        orig = pages[0]
        pool.install_shared(1, list(pages), 5)
        assert pool.counts() == {"free": 7, "used": 0, "shared": 1}
        copies = pool.plan_appends(1)        # both slots write into it
        # Both slots CoW the shared page onto private copies (the pool
        # mutates its per-slot page lists in place, so compare against
        # the captured original id).
        assert len(copies) == 2
        assert all(src == orig for src, _ in copies)
        assert pool.table[0, 0] != pool.table[1, 0]
        assert pool.counts()["shared"] == 0
        assert pool.length_of(0) == pool.length_of(1) == 6

    def test_append_crosses_page_boundary(self):
        pool = KVPagePool(slots=1, capacity=32, page_size=8)
        pool.install_slot(0, 8)              # exactly one full page
        assert pool.plan_appends(1) == []    # fresh page, nothing to copy
        assert len(pool.pages_of(0)) == 2

    def test_rewind_releases_pages(self):
        pool = KVPagePool(slots=1, capacity=32, page_size=8)
        pool.install_slot(0, 8)
        pool.plan_appends(9)                 # -> length 17, 3 pages
        assert len(pool.pages_of(0)) == 3
        pool.rewind(0, 8)
        assert len(pool.pages_of(0)) == 1
        assert pool.length_of(0) == 8
        assert pool.counts()["used"] == 1

    def test_exhaustion_and_reclaim(self):
        pool = KVPagePool(slots=2, capacity=32, page_size=8, pages=3)
        pool.install_slot(0, 16)             # both usable pages
        with pytest.raises(PoolExhaustedError):
            pool.install_slot(1, 8)
        hoard = [pool.pages_of(0)]

        def reclaim():
            if not hoard:
                return False
            pool.free_slot(0)
            hoard.clear()
            return True

        pool.reclaim = reclaim
        pages = pool.install_slot(1, 8)      # succeeds via reclaim
        assert len(pages) == 1


class TestPrefixCache:
    def test_hit_miss_and_refs(self):
        pool = KVPagePool(slots=1, capacity=32, page_size=8)
        cache = PrefixCache(pool, max_entries=2)
        pages = pool.install_slot(0, 5)
        probs = np.full(V, 1.0 / V)
        cache.admit([1, 2, 3, 4, 5], pages, 5, probs)
        pool.free_slot(0)
        # The cache ref keeps the page resident after slot retirement.
        assert pool.counts()["used"] == 1
        assert cache.get([9, 9]) is None
        got = cache.get([1, 2, 3, 4, 5])
        assert got is not None
        g_pages, g_len, g_probs = got
        assert list(g_pages) == pages and g_len == 5
        np.testing.assert_array_equal(g_probs, probs)
        assert cache.hits == 1 and cache.misses == 1
        cache.clear()
        assert pool.free_count == pool.num_pages - 1

    def test_lru_eviction_frees_pages(self):
        pool = KVPagePool(slots=1, capacity=32, page_size=8)
        cache = PrefixCache(pool, max_entries=2)
        for i in range(3):
            pages = pool.install_slot(0, 3)
            cache.admit([i], pages, 3, np.zeros(V))
            pool.free_slot(0)
        assert len(cache) == 2
        assert cache.get([0]) is None        # evicted (LRU)
        assert pool.counts()["used"] == 2


# ----------------------------------------------------- paged bit-identity


class TestPagedBitIdentity:
    def _run(self, lm, kv, prompt, n, **sampling):
        sched = GenerationScheduler(lm, model_name=f"bit_{kv}", slots=3,
                                    kv=kv, page_size=PAGE).start()
        try:
            return sched.generate(prompt, n, timeout_s=120, **sampling)
        finally:
            sched.stop()

    def test_greedy_matches_dense_and_sequential(self, lm):
        prompt = [1, 5, 2, 9, 4]
        ref = _ref(lm, prompt, 10, temperature=0.0)
        assert self._run(lm, "dense", prompt, 10, temperature=0.0) == ref
        assert self._run(lm, "paged", prompt, 10, temperature=0.0) == ref

    def test_seeded_sampling_same_draw_order(self, lm):
        prompt = [2, 7, 1]
        ref = _ref(lm, prompt, 12, temperature=1.0, seed=7)
        out = self._run(lm, "paged", prompt, 12, temperature=1.0, seed=7)
        assert out == ref

    def test_concurrent_slots_page_boundary_crossings(self, lm):
        # Three interleaved sequences of different depths: appends cross
        # page boundaries at different rounds per slot.
        sched = GenerationScheduler(lm, model_name="bit_mix", slots=3,
                                    kv="paged", page_size=PAGE).start()
        try:
            import threading

            prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11]]
            steps = [20, 9, 14]
            outs = [None] * 3

            def client(i):
                outs[i] = sched.generate(prompts[i], steps[i],
                                         temperature=1.0, seed=100 + i,
                                         timeout_s=120)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sched.stop()
        for i in range(3):
            assert outs[i] == _ref(lm, prompts[i], steps[i],
                                   temperature=1.0, seed=100 + i)


# -------------------------------------------- prefix cache + CoW + leaks


class TestPrefixCacheServing:
    def test_repeat_prompt_hits_and_matches(self, lm):
        sched = GenerationScheduler(lm, model_name="pc", slots=2,
                                    kv="paged", page_size=PAGE).start()
        try:
            prompt = [3, 1, 4, 1, 5]
            first = sched.generate(prompt, 8, temperature=0.0,
                                   timeout_s=120)
            h0, m0 = sched._prefix_cache.hits, sched._prefix_cache.misses
            again = sched.generate(prompt, 8, temperature=0.0,
                                   timeout_s=120)
            assert again == first == _ref(lm, prompt, 8, temperature=0.0)
            assert sched._prefix_cache.hits == h0 + 1
            assert sched._prefix_cache.misses == m0
        finally:
            sched.stop()

    def test_cow_divergence_after_shared_prefix(self, lm):
        # Two requests share the cached prefix (one partially-filled tail
        # page); different seeds diverge immediately. CoW must give each
        # its own tail copy — both outputs match their sequential refs.
        sched = GenerationScheduler(lm, model_name="cow", slots=2,
                                    kv="paged", page_size=PAGE).start()
        try:
            import threading

            prompt = [6, 2, 8, 3, 1]  # 5 tokens: tail page shared
            sched.generate(prompt, 1, temperature=0.0, timeout_s=120)
            outs = [None] * 2

            def client(i):
                outs[i] = sched.generate(prompt, 10, temperature=1.0,
                                         seed=40 + i, timeout_s=120)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sched.stop()
        for i in range(2):
            assert outs[i] == _ref(lm, prompt, 10, temperature=1.0,
                                   seed=40 + i)

    def test_cow_pool_accounting(self, lm):
        # Deterministic CoW bookkeeping through the stepper (no decode
        # thread): share a tail page across two slots, then step them
        # with DIFFERENT tokens; each must match the dense stepper's row.
        from deeplearning4j_tpu.models.zoo import (DecodeStepper,
                                                   PagedDecodeStepper)

        prompt = [1, 2, 3, 4, 5]
        paged = PagedDecodeStepper(lm, 2, page_size=PAGE)
        dense = DecodeStepper(lm, 2)
        probs, state, n = paged.prefill(prompt, pad_to=8)
        paged.install(0, state, n)
        paged.install_shared(1, paged.pool.pages_of(0), n)
        assert paged.pool.counts()["shared"] == 1
        dprobs, dstate, dn = dense.prefill(prompt, pad_to=8)
        dense.install(0, dstate, dn)
        dense.install(1, dstate, dn)
        p = paged.step([7, 11])
        d = dense.step([7, 11])
        assert paged.pool.counts()["shared"] == 0  # both tails CoW'd
        np.testing.assert_array_equal(p, d)
        p2 = paged.step([int(p[0].argmax()), int(p[1].argmax())])
        d2 = dense.step([int(d[0].argmax()), int(d[1].argmax())])
        np.testing.assert_array_equal(p2, d2)

    def test_no_page_leak_after_recycle_and_deadline(self, lm):
        sched = GenerationScheduler(lm, model_name="leak", slots=2,
                                    kv="paged", page_size=PAGE).start()
        pool = sched.stepper.pool
        baseline = pool.num_pages - 1
        try:
            for i in range(3):
                sched.generate([1 + i, 2, 3], 6, temperature=1.0, seed=i,
                               timeout_s=120)
            # Deadline expiry mid-generation: slot recycled at the next
            # step boundary, pages freed.
            with pytest.raises(Exception):
                sched.generate([9, 9, 9, 9], 25, temperature=1.0,
                               timeout_s=0.001)
            deadline = time.monotonic() + 10
            while pool.tracked() and time.monotonic() < deadline:
                time.sleep(0.02)
            assert not pool.tracked(), "slot not recycled after deadline"
            sched._prefix_cache.clear()
            assert pool.free_count == baseline, pool.counts()
        finally:
            sched.stop()


# ------------------------------------------------------------ speculative


class TestSpeculativeDecoding:
    def test_exactness_gate_vs_non_speculative(self, lm, draft_lm):
        prompt = [1, 5, 2, 9, 4]
        ref = _ref(lm, prompt, 10, temperature=0.0)
        sched = GenerationScheduler(lm, model_name="spec", slots=2,
                                    kv="paged", page_size=PAGE,
                                    draft=draft_lm, spec_k=3).start()
        try:
            assert sched.generate(prompt, 10, temperature=0.0,
                                  timeout_s=120) == ref
            # Near-capacity: k_round clamps to the remaining budget.
            edge = [3, 3, 8]
            assert sched.generate(edge, CAP - 3, temperature=0.0,
                                  timeout_s=120) == _ref(
                                      lm, edge, CAP - 3, temperature=0.0)
            # Sampled requests stay on the sequential draw order (one
            # token per round from row 0).
            assert sched.generate(prompt, 8, temperature=1.0, seed=5,
                                  timeout_s=120) == _ref(
                                      lm, prompt, 8, temperature=1.0,
                                      seed=5)
        finally:
            sched.stop()

    def test_identical_draft_accepts(self, lm, lm_twin):
        from deeplearning4j_tpu.serving import metrics as _m

        sched = GenerationScheduler(lm, model_name="spec_twin", slots=2,
                                    kv="paged", page_size=PAGE,
                                    draft=lm_twin, spec_k=3).start()
        try:
            prompt = [2, 4, 6]
            out = sched.generate(prompt, 12, temperature=0.0,
                                 timeout_s=120)
            assert out == _ref(lm, prompt, 12, temperature=0.0)
        finally:
            sched.stop()
        acc = _m.SPECULATIVE_TOKENS.labels(model="spec_twin",
                                           outcome="accepted")
        # Identical weights -> the target's argmax always agrees with the
        # draft's: speculation actually emits multiple tokens per step.
        assert acc._value > 0

    def test_spec_requires_draft_knobs(self, lm, draft_lm):
        with pytest.raises(ValueError):
            GenerationScheduler(lm, kv="dense", prefix_cache=True)
        with pytest.raises(ValueError):
            GenerationScheduler(lm, kv="paged", draft=draft_lm, spec_k=0)
        with pytest.raises(ValueError):
            GenerationScheduler(lm, kv="nope")


class TestInstallIsOneShape:
    def test_no_compile_for_a_new_page_count_after_warmup(self):
        """`install` used to scatter only the pages a prompt needs: each
        distinct page count compiled ten eager gather/scatter programs —
        under traffic, after a warm-up that had installed one page (seen
        first on the chip, where real prompts span several pages)."""
        from deeplearning4j_tpu import observability as obs

        obs.install_jax_compile_hook(obs.metrics)

        def compiles():
            fam = obs.metrics.get_family("dl4j_xla_compiles_total")
            return sum(c.get() for c in fam.children()) if fam else 0.0

        lm = _lm()
        sched = GenerationScheduler(lm, kv="paged", page_size=PAGE, slots=2,
                                    prompt_buckets=(8, 16, 32)).start()
        try:
            sched.warmup()  # installs a 1-token prompt: one page
            before = compiles()
            prompts = [[1 + (i % (V - 1)) for i in range(n)]
                       for n in (3, 9, 20, 27)]  # 1, 2, 3, 4 pages of 8
            served = [sched.generate(p, 2, temperature=0.0, timeout_s=60)
                      for p in prompts]
            assert compiles() == before
        finally:
            sched.stop()
        for p, got in zip(prompts, served):  # the references compile theirs
            assert list(got) == list(_ref(lm, p, 2, temperature=0.0))


# ---------------------------------------------------------------- metrics


class TestPagedMetricsScrape:
    def test_one_scrape_carries_paged_families(self, lm, lm_twin):
        server = InferenceServer(lm, port=0, kv_cache="paged",
                                 kv_page_size=PAGE, draft=lm_twin,
                                 spec_k=2).start()
        try:
            prompt = [1, 2, 3, 4]
            server.generate(prompt, 6, temperature=0.0)
            server.generate(prompt, 6, temperature=0.0)  # prefix hit
            with urllib.request.urlopen(server.url + "/metrics",
                                        timeout=10) as r:
                scrape = r.read().decode()
        finally:
            server.stop()
        for needle in (
                'dl4j_kv_pages{model="default",state="free"}',
                'dl4j_kv_pages{model="default",state="used"}',
                'dl4j_kv_pages{model="default",state="shared"}',
                'dl4j_prefix_cache_hits_total{model="default"}',
                'dl4j_prefix_cache_misses_total{model="default"}',
                'dl4j_speculative_tokens_total{model="default",'
                'outcome="accepted"}',
                'dl4j_speculative_tokens_total{model="default",'
                'outcome="rejected"}',
                # existing serving families still ride the same scrape
                'dl4j_serving_ttft_seconds_bucket{model="default"',
                'dl4j_serving_generated_tokens_total{model="default"}',
        ):
            assert needle in scrape, f"missing {needle} in /metrics"


class TestShardedServing:
    def test_sharded_programs_are_acquired_inside_the_context(
            self, monkeypatch):
        """Every program a model-parallel server builds — the batcher's
        /predict buckets as well as the decode stepper's — is traced
        inside the model's ParallelContext. Outside it the kernel registry
        cannot see the mesh, and on a TPU resolves Pallas bodies the
        compiler refuses in a partitioned program (found on four chips:
        the batcher's warm-up ran outside the context)."""
        import jax

        from deeplearning4j_tpu.compilation import program
        from deeplearning4j_tpu.parallel.context import current_context

        outside = []
        acquire = program.CachedProgram._acquire

        def spy(self, args):
            ctx = current_context()
            if ctx is None or ctx.mesh.devices.size == 1:
                if any(len(getattr(leaf, "sharding").device_set) > 1
                       for leaf in jax.tree_util.tree_leaves(args)
                       if hasattr(leaf, "sharding")):
                    outside.append((self.kind, dict(self.static)))
            return acquire(self, args)

        monkeypatch.setattr(program.CachedProgram, "_acquire", spy)
        server = InferenceServer(_lm(), port=0, kv_cache="paged",
                                 kv_page_size=PAGE, decode_slots=2,
                                 max_batch_size=2, warmup=True,
                                 model_parallel=4).start()
        try:
            assert server.wait_ready(timeout=120)
            out = server.predict(np.ones((1, 16), np.int32))
            assert out.shape == (1, 16, V)
            server.generate([1, 2, 3], 3, temperature=0.0)
        finally:
            server.stop()
        assert outside == []

    def test_model_parallel_serving_matches_unsharded(self):
        """PR 20 end to end at the server tier: a 4-way tensor-parallel
        paged LM serves the same greedy completion as an unsharded one,
        /v1/models + the sharding info gauge report the layout, and the
        sharded jit programs are fingerprint-distinct (the parallel
        context folds into the jit-cache key), so zero serving-path
        recompiles on later identical requests."""
        import json as _json

        ref_srv = InferenceServer(_lm(), port=0, kv_cache="paged",
                                  kv_page_size=PAGE, decode_slots=2).start()
        sh_srv = InferenceServer(_lm(), port=0, kv_cache="paged",
                                 kv_page_size=PAGE, decode_slots=2,
                                 model_parallel=4).start()
        try:
            prompt = [1, 2, 3, 4, 5]
            want = ref_srv.generate(prompt, 8, temperature=0.0)
            got = sh_srv.generate(prompt, 8, temperature=0.0)
            assert list(got) == list(want)
            with urllib.request.urlopen(sh_srv.url + "/v1/models",
                                        timeout=10) as r:
                rows = {m["name"]: m
                        for m in _json.loads(r.read())["models"]}
            assert rows["default"]["sharding"] == "model:4-way"
            with urllib.request.urlopen(sh_srv.url + "/metrics",
                                        timeout=10) as r:
                scrape = r.read().decode()
            assert ('dl4j_serving_model_sharding{model="default",'
                    'sharding="model:4-way"} 1' in scrape
                    or 'dl4j_serving_model_sharding{sharding='
                    '"model:4-way",model="default"} 1' in scrape)
        finally:
            ref_srv.stop()
            sh_srv.stop()
