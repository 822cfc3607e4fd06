"""Multi-tenant LoRA serving: segmented kernel parity, AdapterPool
lifecycle (load / LRU evict / transparent reload / lease safety),
engine + router integration, migration re-pinning, and the composed
quantized-base + LoRA config (PR-14)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
from tpudl.models.lora import (
    extract_adapters,
    merge_adapter,
    strip_adapters,
)
from tpudl.obs import registry
from tpudl.serve import AdapterPool, Request, ServeSession
from tpudl.serve.lora import assert_tenant_parity

#: Deliberately tiny: every test here compiles its own lora programs
#: on CPU, so model size is test wall-time.
TINY = dict(
    vocab_size=128,
    hidden_size=32,
    num_layers=1,
    num_heads=2,
    num_kv_heads=1,
    intermediate_size=64,
    max_seq_len=64,
    rope_theta=10_000.0,
    dtype=jnp.float32,
)
PROMPT_LEN = 8


@pytest.fixture(scope="module")
def base():
    cfg = LlamaConfig(**TINY)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def make_adapter(seed: int, rank: int = 2, b_scale: float = 0.05):
    cfg = LlamaConfig(**TINY, lora_rank=rank)
    lp = LlamaForCausalLM(cfg).init(
        jax.random.key(seed), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    flat = extract_adapters(lp)
    rng = np.random.default_rng(seed)
    return {
        path: {
            "lora_a": np.asarray(f["lora_a"]),
            "lora_b": rng.normal(
                scale=b_scale, size=np.shape(f["lora_b"])
            ).astype(np.float32),
        }
        for path, f in flat.items()
    }


@pytest.fixture(scope="module")
def adapters():
    # Ragged ranks on purpose: tenant "t2" is rank 1 under r_max 2, so
    # its unused table entry exercises the zero-page contract.
    return {
        "t0": make_adapter(1),
        "t1": make_adapter(2),
        "t2": make_adapter(3, rank=1),
    }


def tenant_requests(tenants, n=6, seed=0, max_new=(4, 10)):
    rng = np.random.default_rng(seed)
    cycle = [None] + list(tenants)
    return [
        Request(
            request_id=f"r{seed}-{i}",
            input_ids=rng.integers(
                1, 100, size=int(rng.integers(2, PROMPT_LEN + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(*max_new)),
            tenant=cycle[i % len(cycle)],
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# the segmented kernel
# ---------------------------------------------------------------------------


def test_segmented_lora_fused_matches_reference():
    """Pallas (interpret) vs XLA composite on ragged tables: empty
    slots, short ranks via zero pages, f32 and int8 pools, [B, H] and
    [B, S, H] activations."""
    from tpudl.ops.segmented_lora import segmented_lora

    rng = np.random.default_rng(0)
    np_, h, o, p = 9, 24, 40, 3
    pools = {
        "a": jnp.asarray(rng.normal(size=(np_, h)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(np_, o)), jnp.float32),
    }
    # Page 0 is the all-zero page by contract.
    pools = {
        "a": pools["a"].at[0].set(0.0), "b": pools["b"].at[0].set(0.0)
    }
    table = np.array(
        [[1, 2, 3], [4, 0, 0], [0, 0, 0], [5, 6, 0]], np.int32
    )
    scale = np.array([0.5, 2.0, 0.0, 1.0], np.float32)
    x = jnp.asarray(rng.normal(size=(4, 2, h)), jnp.float32)
    ref = segmented_lora(x, pools, table, scale, impl="reference")
    fused = segmented_lora(x, pools, table, scale, impl="fused")
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(fused), rtol=2e-5, atol=2e-6
    )
    # Hand-computed row 0 (full-rank slot).
    a = np.asarray(pools["a"])[table[0]].T
    b = np.asarray(pools["b"])[table[0]]
    want = 0.5 * (np.asarray(x)[0] @ a) @ b
    np.testing.assert_allclose(np.asarray(ref)[0], want, rtol=1e-5)
    # Empty slot contributes exactly zero.
    assert np.abs(np.asarray(fused)[2]).max() == 0.0
    # int8 pools with per-page scales.
    qa = np.clip(
        np.round(np.asarray(pools["a"]) / 0.01), -127, 127
    ).astype(np.int8)
    qb = np.clip(
        np.round(np.asarray(pools["b"]) / 0.02), -127, 127
    ).astype(np.int8)
    qpools = {
        "a": jnp.asarray(qa), "b": jnp.asarray(qb),
        "a_scale": jnp.full((np_,), 0.01, jnp.float32),
        "b_scale": jnp.full((np_,), 0.02, jnp.float32),
    }
    r8 = segmented_lora(x, qpools, table, scale, impl="reference")
    f8 = segmented_lora(x, qpools, table, scale, impl="fused")
    np.testing.assert_allclose(
        np.asarray(r8), np.asarray(f8), rtol=2e-5, atol=2e-6
    )
    # 2-D activation form.
    r2 = segmented_lora(x[:, 0], pools, table, scale, impl="fused")
    np.testing.assert_allclose(
        np.asarray(r2), np.asarray(fused)[:, 0], rtol=1e-6
    )


# ---------------------------------------------------------------------------
# AdapterPool lifecycle
# ---------------------------------------------------------------------------


def test_adapter_pool_register_validates(base, adapters):
    model, _ = base
    pool = AdapterPool(model.cfg, r_max=2, num_slots=2, num_pages=9)
    with pytest.raises(ValueError, match="no lora_a"):
        pool.register("empty", {})
    bad = {
        "model/layer_0/attention/q_proj": {
            "lora_a": np.zeros((7, 2), np.float32),  # wrong in-dim
            "lora_b": np.zeros((2, 32), np.float32),
        }
    }
    with pytest.raises(ValueError, match="do not fit site"):
        pool.register("bad", bad)
    big = make_adapter(9, rank=4)
    with pytest.raises(ValueError, match="outside"):
        pool.register("big", big)  # rank 4 > r_max 2
    with pytest.raises(ValueError, match="not an adaptable site"):
        pool.register("alien", {
            "model/layer_0/lm_head": {
                "lora_a": np.zeros((32, 2), np.float32),
                "lora_b": np.zeros((2, 32), np.float32),
            }
        })


def test_adapter_pool_lru_eviction_and_lease_safety(base, adapters):
    """Satellite: refcount-0 LRU reclaim under pressure; an adapter
    leased by a seated request is NEVER evicted mid-decode."""
    model, _ = base
    # Room for exactly two rank-2 adapters (pages 1..4 + zero page).
    pool = AdapterPool(model.cfg, r_max=2, num_slots=2, num_pages=5)
    for tid, tree in adapters.items():
        pool.register(tid, tree)
    row0, _ = pool.acquire("t0")
    assert set(row0[row0 != 0]) and pool.resident_since("t0") is not None
    pool.release("t0")  # refcount 0: cached, evictable
    pool.acquire("t1")
    pool.release("t1")
    assert pool.stats()["resident"] == 2 and pool.free_pages == 0
    # Loading t2 (rank 1) under pressure evicts the LRU refcount-0
    # resident — t0, the older stamp.
    pool.acquire("t2")
    stats = pool.stats()
    assert stats["evictions"] == 1
    assert pool.resident_since("t0") is None, "LRU victim should be t0"
    assert pool.resident_since("t1") is not None
    pool.release("t2")
    # Lease safety: pin t1 and t2 (3 pages), then t0 (2 pages) cannot
    # load — only 1 page is reclaimable and NO leased adapter may be
    # touched.
    pool.acquire("t1")
    pool.acquire("t2")
    assert not pool.can_seat("t0")
    with pytest.raises(RuntimeError, match="leased"):
        pool.acquire("t0")
    assert pool.resident_since("t1") is not None
    assert pool.resident_since("t2") is not None
    pool.release("t1")
    pool.release("t2")
    # Pressure relieved: t0 reloads (its pages were reclaimed).
    pool.acquire("t0")
    assert pool.stats()["reloads"] >= 1
    pool.release("t0")


def test_adapter_pool_nbytes_reconciles_with_buffers(base, adapters):
    """Satellite (the PR-8 byte-accounting idiom): ``nbytes`` — the
    number ``serve_adapters_per_gb`` divides into — must equal the sum
    of the ACTUAL buffer nbytes (int8 values AND f32 scale rows AND
    the host slot tables), not a dtype-assuming estimate."""
    model, _ = base
    for dtype in (None, "int8"):
        pool = AdapterPool(
            model.cfg, r_max=2, num_slots=4, num_pages=9, dtype=dtype
        )
        device = sum(
            leaf.nbytes for leaf in jax.tree.leaves(pool.pools)
        )
        want = device + pool.slot_table.nbytes + pool.slot_scale.nbytes
        assert pool.nbytes == want
        assert pool.bytes_per_page * pool.num_pages == device
        if dtype == "int8":
            # Scale rows are f32 and must be inside the accounting:
            # an int8 pool without them would under-report.
            scale_bytes = sum(
                leaf.nbytes
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    pool.pools
                )[0]
                if "scale" in jax.tree_util.keystr(path)
            )
            assert scale_bytes > 0
        # Capacity arithmetic follows the same bytes.
        assert pool.adapters_per_gb(2) == 1e9 / (pool.bytes_per_page * 2)


def test_evicted_tenant_reloads_transparently(base, adapters):
    """Satellite: after eviction, the tenant's NEXT request reloads
    the adapter with no caller-visible difference — same tokens as an
    always-resident run — and serve_adapter_reloads_total counts it."""
    model, params = base
    # Pool holds ONE rank-2 adapter: t0 and t1 must thrash.
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"], "t1": adapters["t1"]},
        adapter_pages=3,
    )
    reloads0 = registry().counter("serve_adapter_reloads_total").value
    r0 = Request("a", [3, 4, 5], max_new_tokens=4, tenant="t0")
    r1 = Request("b", [3, 4, 5], max_new_tokens=4, tenant="t1")
    r2 = Request("c", [3, 4, 5], max_new_tokens=4, tenant="t0")
    out0 = session.serve([r0])  # loads t0
    out1 = session.serve([r1])  # evicts t0, loads t1
    out2 = session.serve([r2])  # transparent reload of t0
    assert out0["a"].ok and out1["b"].ok and out2["c"].ok
    assert out2["c"].tokens == out0["a"].tokens, (
        "a reloaded adapter must serve identical tokens"
    )
    pool = session.engine.adapter_pool
    assert pool.stats()["evictions"] >= 1
    assert pool.stats()["reloads"] >= 1
    assert (
        registry().counter("serve_adapter_reloads_total").value
        > reloads0
    )
    # And the reference is still the merged adapter, not the base.
    merged = merge_adapter(params, adapters["t0"])
    from tpudl.models.generate import generate

    want = np.asarray(generate(
        model, merged, jnp.asarray([[3, 4, 5]], jnp.int32),
        max_new_tokens=4,
    ))[0]
    np.testing.assert_array_equal(np.asarray(out2["c"].tokens), want)


# ---------------------------------------------------------------------------
# engine parity (the acceptance gates)
# ---------------------------------------------------------------------------


def test_multi_tenant_parity_exact_f32(base, adapters):
    """The heterogeneous batch — mixed tenants + tenantless slots,
    ragged ranks — serves EXACT tokens vs the sequential
    one-adapter-at-a-time merged reference, through BOTH kernel paths
    (Pallas interpret and XLA composite)."""
    model, params = base
    reqs = tenant_requests(adapters, n=7, seed=0)
    for impl in ("fused", "reference"):
        session = ServeSession.from_model(
            model, params, prompt_len=PROMPT_LEN, num_slots=4,
            adapters=adapters, adapter_impl=impl,
        )
        assert_tenant_parity(
            session, model, params, adapters, reqs, atol=None
        )


def test_multi_tenant_parity_int8_pages_margin(base, adapters):
    """int8 adapter pages: a greedy flip must be a genuine near-tie
    under the teacher-forced logit margin (per-tenant merged
    reference). alpha=4 keeps the page-quantization error at weight-
    cell scale — the contract the grid's lora8 cell pins."""
    model, params = base
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=4,
        adapters=adapters, adapter_dtype="int8", adapter_alpha=4.0,
    )
    assert_tenant_parity(
        session, model, params, adapters,
        tenant_requests(adapters, n=6, seed=1),
        atol=0.1, alpha=4.0,
    )


def test_quantized_base_composes_with_adapters(base, adapters):
    """The lifted mutual exclusion, serving half: int8 BASE weights +
    per-tenant f32 adapters in one session (margin parity vs the f32
    merged reference — exactly the int8-weight cell's contract, now
    with adapters on top)."""
    model, params = base
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=4,
        adapters=adapters, weight_dtype="int8",
    )
    assert_tenant_parity(
        session, model, params, adapters,
        tenant_requests(adapters, n=5, seed=2, max_new=(4, 7)),
        atol=0.1,
    )


# ---------------------------------------------------------------------------
# config composition (satellite: the lifted raise)
# ---------------------------------------------------------------------------


def test_lora_rank_weight_dtype_compose_in_config():
    """LlamaConfig(weight_dtype=..., lora_rank>0) no longer raises:
    the projection runs a LoRADense over a quantized base kernel, and
    quantize_model on a LoRA tree quantizes ONLY the base kernels."""
    from tpudl.quant import quantize_model
    from tpudl.quant.quantize import is_quantized

    cfg = LlamaConfig(**TINY, lora_rank=2)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(1), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    qmodel, qparams = quantize_model(model, params, "int8")
    assert qmodel.cfg.weight_dtype == "int8"
    assert qmodel.cfg.lora_rank == 2
    site = qparams["model"]["layer_0"]["attention"]["q_proj"]
    assert is_quantized(site["kernel"])
    assert site["lora_a"].dtype == jnp.float32  # adapters stay full
    ids = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    q_logits = qmodel.apply({"params": qparams}, ids)
    # Reference: dequantize the base, run the plain lora model.
    from tpudl.quant import dequantize_tree

    ref_logits = model.apply({"params": dequantize_tree(qparams)}, ids)
    np.testing.assert_allclose(
        np.asarray(q_logits), np.asarray(ref_logits),
        rtol=5e-2, atol=5e-2,
    )


def test_lora_rank_validation():
    with pytest.raises(ValueError, match="lora_rank"):
        LlamaConfig(**TINY, lora_rank=-1)


def test_adapter_helpers_roundtrip(base, adapters):
    """strip/extract/merge are consistent: stripping a LoRA tree
    yields the base structure, and merging the extracted adapter
    reproduces LoRADense's own math."""
    model, params = base
    cfg = LlamaConfig(**TINY, lora_rank=2)
    lmodel = LlamaForCausalLM(cfg)
    lp = lmodel.init(
        jax.random.key(5), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    flat = extract_adapters(lp)
    assert all("lora_a" in f and "lora_b" in f for f in flat.values())
    base_tree = strip_adapters(lp)
    assert not extract_adapters(base_tree)
    ids = jnp.asarray([[5, 6, 7]], jnp.int32)
    merged = merge_adapter(base_tree, flat, alpha=16.0)
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": merged}, ids)),
        np.asarray(lmodel.apply({"params": lp}, ids)),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# admission / config errors
# ---------------------------------------------------------------------------


def test_tenant_admission_validation(base, adapters):
    model, params = base
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"]},
    )
    with pytest.raises(ValueError, match="unknown tenant"):
        session.submit(Request("x", [1, 2], 2, tenant="nobody"))
    plain = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2
    )
    with pytest.raises(ValueError, match="serves no adapters"):
        plain.submit(Request("y", [1, 2], 2, tenant="t0"))
    with pytest.raises(ValueError, match="prefix_share"):
        ServeSession.from_model(
            model, params, prompt_len=PROMPT_LEN, num_slots=2,
            adapters={"t0": adapters["t0"]}, prefix_share=True,
        )
    with pytest.raises(ValueError, match="spec_k"):
        ServeSession.from_model(
            model, params, prompt_len=PROMPT_LEN, num_slots=2,
            adapters={"t0": adapters["t0"]}, spec_k=2,
        )


# ---------------------------------------------------------------------------
# migration: the tenant id rides the payload
# ---------------------------------------------------------------------------


def test_migration_repins_adapter_on_target(base, adapters):
    """Engine-level migration of a seated tenant request: the payload
    carries the tenant id, the target pool loads + pins the adapter
    before KV lands, and the resumed stream is byte-exact vs the
    merged reference."""
    from tpudl.models.generate import generate

    model, params = base
    mk = lambda: ServeSession.from_model(  # noqa: E731
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"], "t1": adapters["t1"]},
    )
    src, dst = mk(), mk()
    req = Request("mig", [9, 8, 7, 6], max_new_tokens=16, tenant="t0")
    src.submit(req)
    for _ in range(5):
        src.engine.step()
    payload = src.engine.export_request("mig")
    assert payload is not None
    from tpudl.serve.cache import parse_migration

    assert parse_migration(payload)["request"]["tenant"] == "t0"
    assert dst.engine.adapter_pool.resident_since("t0") is None
    dst.engine.install_migrated(payload)
    # Re-pinned BEFORE decode resumed; zero prefills on the target.
    assert dst.engine.adapter_pool.resident_since("t0") is not None
    assert dst.engine.num_prefills == 0
    while dst.engine.step():
        pass
    res = dst.engine.results["mig"]
    assert res.ok
    merged = merge_adapter(params, adapters["t0"])
    want = np.asarray(generate(
        model, merged, jnp.asarray([[9, 8, 7, 6]], jnp.int32),
        max_new_tokens=16,
    ))[0]
    np.testing.assert_array_equal(np.asarray(res.tokens), want)


def test_migration_refused_without_target_pool(base, adapters):
    """A tenant payload must NOT resume on an engine that cannot serve
    the tenant — it fails loudly instead of decoding the bare base."""
    from tpudl.serve.cache import MigrationCompatError

    model, params = base
    src = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"]},
    )
    dst = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2
    )
    req = Request("m2", [4, 5, 6], max_new_tokens=8, tenant="t0")
    src.submit(req)
    for _ in range(3):
        src.engine.step()
    payload = src.engine.export_request("m2")
    with pytest.raises(MigrationCompatError, match="adapter pool"):
        dst.engine.install_migrated(payload)


# ---------------------------------------------------------------------------
# router: quotas, classes, affinity
# ---------------------------------------------------------------------------


def test_router_tenant_quota_and_priority(base, adapters):
    """Per-tenant classes on the existing priority ladder: the class
    priority is applied at the door, and the in-flight token quota
    sheds the excess as shed_quota."""
    from tpudl.serve import Replica, Router

    model, params = base
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"]},
    )
    # Warm so the replica thread never sits in a first-call compile.
    session.serve([Request("w", [1, 2], 2, tenant="t0")])
    router = Router(
        [Replica("r0", session)],
        tenant_classes={
            "t0": {"priority": 2, "max_inflight_tokens": 10}
        },
    )
    try:
        reqs = [
            Request(f"q{i}", [3, 4, 5], max_new_tokens=5, tenant="t0")
            for i in range(5)
        ]
        for r in reqs:
            router.submit(r)
        out = router.collect(timeout_s=120)
        reasons = sorted(r.finish_reason for r in out.values())
        assert reasons.count("shed_quota") == 3, reasons  # 2 fit 10 tokens
        served = [r for r in out.values() if r.ok]
        assert len(served) == 2
    finally:
        router.close()


def test_router_places_tenant_only_on_serving_replica(base, adapters):
    """Review regression: a heterogeneous fleet where only SOME
    replicas serve a tenant must route its requests there — the
    least-loaded fallback picking a non-serving replica would
    terminally reject them at the replica door."""
    from tpudl.serve import Replica, Router

    model, params = base
    s_plain = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2
    )
    s_lora = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"]},
    )
    for s in (s_plain, s_lora):
        s.serve([Request("w", [1, 2], 2)])
    # The plain replica starts least-loaded AND first in the list.
    router = Router([Replica("plain", s_plain), Replica("lora", s_lora)])
    try:
        out = router.serve(
            [
                Request(f"t{i}", [4, 5], max_new_tokens=3, tenant="t0")
                for i in range(3)
            ],
            timeout_s=120,
        )
        assert all(r.ok for r in out.values()), {
            k: v.finish_reason for k, v in out.items()
        }
        assert s_lora.engine.adapter_pool.resident_since("t0") is not None
    finally:
        router.close()


def test_reregister_swaps_factors_and_refuses_leased(base, adapters):
    """Review regression: re-registering a tenant whose v1 pages are
    still cached (refcount 0) must invalidate them — the next acquire
    loads v2, not the stale pages the refreshed LRU stamp would keep
    alive. A LEASED residency refuses the swap."""
    model, _ = base
    pool = AdapterPool(model.cfg, r_max=2, num_slots=2, num_pages=9)
    pool.register("t", adapters["t0"])
    row_v1, _ = pool.acquire("t")
    pool.release("t")  # cached at refcount 0
    del row_v1
    pool.register("t", adapters["t1"])  # v2
    assert pool.resident_since("t") is None, (
        "stale v1 residency must be invalidated by re-registration"
    )
    row_v2, _ = pool.acquire("t")
    # v2 really is what loaded: the first page's A row holds t1's
    # first rank column, not t0's.
    got = np.asarray(
        pool.pools["layer_0"]["q_proj"]["a"][int(row_v2[0])]
    )
    want = np.asarray(
        adapters["t1"]["model/layer_0/attention/q_proj"]["lora_a"]
    )[:, 0]
    np.testing.assert_array_equal(got, want)
    # Leased: the swap must refuse instead of ripping pages out from
    # under a seated request.
    with pytest.raises(ValueError, match="leased"):
        pool.register("t", adapters["t0"])
    pool.release("t")
    pool.register("t", adapters["t0"])  # refcount 0 again: fine


def test_seat_failure_releases_adapter_pin(base, adapters):
    """Review regression: a cache-seat exception between acquire and
    bind must release the tenant pin — a leaked refcount would make
    the adapter unevictable for the process lifetime."""
    model, params = base
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"]},
    )
    engine = session.engine
    pool = engine.adapter_pool
    orig_seat = engine.cache.seat

    def boom(*args, **kwargs):
        raise RuntimeError("injected seat failure")

    engine.cache.seat = boom
    session.submit(Request("x", [1, 2, 3], max_new_tokens=4, tenant="t0"))
    with pytest.raises(RuntimeError, match="injected seat failure"):
        engine.step()
    engine.cache.seat = orig_seat
    assert pool.stats()["leased"] == 0, (
        "the failed seat leaked its tenant pin"
    )
    # The adapter is still fully usable (and evictable) afterwards.
    pool.acquire("t0")
    pool.release("t0")


def test_router_adapter_affinity(base, adapters):
    """A tenant's requests stick to the replica whose pool already
    holds its adapter (longest-resident wins), instead of loading the
    adapter everywhere."""
    from tpudl.serve import Replica, Router

    model, params = base
    mk = lambda: ServeSession.from_model(  # noqa: E731
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        adapters={"t0": adapters["t0"], "t1": adapters["t1"]},
    )
    s0, s1 = mk(), mk()
    for s in (s0, s1):
        s.serve([Request("w", [1, 2], 2)])  # warm compile, no tenant
    # Make t0 resident on s1 ONLY, before the router exists.
    s1.engine.adapter_pool.acquire("t0")
    s1.engine.adapter_pool.release("t0")
    router = Router([Replica("r0", s0), Replica("r1", s1)])
    try:
        for i in range(4):
            router.submit(Request(
                f"a{i}", [2, 3, 4], max_new_tokens=3, tenant="t0"
            ))
        out = router.collect(timeout_s=120)
        assert all(r.ok for r in out.values())
        # Every t0 request must have landed on r1: r0's pool never
        # loaded the adapter.
        assert s0.engine.adapter_pool.resident_since("t0") is None
        assert s1.engine.adapter_pool.stats()["loads"] == 1
    finally:
        router.close()
