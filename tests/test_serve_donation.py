"""The paged KV pool changes hands, it is not copied (ISSUE 25).

Every compiled program that takes ``PagedKVCache.cache`` and returns
its successor donates it. Three bars:

- ``tpudl.analysis.donation.audit_donation`` passes for the session's
  own decode, verify and seat programs and for the migration scatter,
  on bf16 and on int8 pools: every old leaf consumed, every buffer
  reused in place;
- a session that served requests counts no pool copy
  (``serve_kv_pool_copies`` reads 0) and a pool tree kept from before
  the run is dead, whichever way the session was built (plain, int8,
  prefix sharing, speculation, tenant adapters, from artifacts, on a
  mesh); a program jitted without donation makes the counter rise;
- donation changes no token: a donating session serves what a session
  over the same programs without donation serves.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.analysis.donation import audit_donation
from tpudl.models.generate import paged_decode_fn, prefill_fn
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.obs import registry
from tpudl.serve import PagedKVCache, Request, ServeSession
from tpudl.serve.cache import _migration_scatter, parse_migration

CFG = LLAMA_TINY(max_seq_len=64)  # bf16, as the benchmark's pools are
PROMPT_LEN = 16
PAGE = 4
SLOTS = 2
KV_DTYPES = [None, "int8"]
KV_IDS = ["bf16", "int8"]


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _session(model, params, **kw):
    kw.setdefault("prompt_len", PROMPT_LEN)
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("page_size", PAGE)
    return ServeSession.from_model(model, params, **kw)


def _requests(n, seed=0, tag="r", shared=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, CFG.vocab_size, size=shared).tolist()
    return [
        Request(
            f"{tag}{i}",
            head + rng.integers(
                1, CFG.vocab_size,
                size=int(rng.integers(2, PROMPT_LEN - shared + 1)),
            ).tolist(),
            max_new_tokens=int(rng.integers(4, 9)),
        )
        for i in range(n)
    ]


def _copies() -> float:
    return registry().counter("serve_kv_pool_copies").value


def _row_cache(model, params, n_tokens=PROMPT_LEN):
    """A batch-1 dense prefill row, as the engine hands one to a seat."""
    ids = np.arange(1, n_tokens + 1, dtype=np.int32)[None, :]
    _, row = jax.jit(prefill_fn(model))(params, ids, np.ones_like(ids))
    return row


# ---------------------------------------------------------------------------
# the audit, program by program
# ---------------------------------------------------------------------------


def _audit_decode_contract(program, eng, params, window=()):
    """Audit a decode-contract program on the engine's own pool, fed
    what the engine feeds it (``window``: the verify chunk's width)."""
    tokens = np.zeros((eng.num_slots, *window), np.int32)
    args = (
        params, eng.cache.cache, tokens, tokens,
        *eng.cache.dispatch_args(),
    )
    (_, pool), report = audit_donation(program, args, donate_argnums=(1,))
    return pool, report


def _audit_decode(model, params, kv_dtype):
    eng = _session(model, params, kv_dtype=kv_dtype).engine
    return _audit_decode_contract(eng.decode_call, eng, params)


def _audit_verify(model, params, kv_dtype):
    eng = _session(model, params, kv_dtype=kv_dtype, spec_k=2).engine
    return _audit_decode_contract(eng.verify_call, eng, params, window=(2,))


def _audit_seat(model, params, kv_dtype):
    cache = _session(model, params, kv_dtype=kv_dtype).engine.cache
    row = _row_cache(model, params)
    # The first seat builds (and runs) the program; the audit runs it
    # again on the pool that seat left.
    cache.seat(row, 0, pad=0, prompt_len=PROMPT_LEN,
               reserve_tokens=PROMPT_LEN + 4)
    pages = jnp.asarray(cache.page_table[0, : PROMPT_LEN // PAGE])
    return audit_donation(
        cache._seat_program(PROMPT_LEN // PAGE), (cache.cache, row, pages)
    )


def _audit_seat_shared(model, params, kv_dtype):
    cache = _session(
        model, params, kv_dtype=kv_dtype, prefix_share=True
    ).engine.cache
    row = _row_cache(model, params)
    ids = np.arange(1, PROMPT_LEN + 1, dtype=np.int32)
    cache.seat_shared(row, 0, ids, reserve_tokens=PROMPT_LEN + 4)
    page_ids = np.zeros((cache.pages_per_slot,), np.int32)
    return audit_donation(
        cache._seat_shared_fn,
        (cache.cache, row, jnp.asarray(page_ids), jnp.int32(0)),
    )


def _audit_migration_scatter(model, params, kv_dtype):
    src = _session(model, params, kv_dtype=kv_dtype)
    cache = _session(model, params, kv_dtype=kv_dtype).engine.cache
    req = _requests(1)[0]
    src.submit(req)
    src.engine.step()
    meta = parse_migration(src.engine.export_request(req.request_id))
    rows = cache._migration_rows(
        meta, int(meta["lens"]), int(meta["skip_tokens"])
    )
    page_ids = np.zeros((cache.pages_per_slot,), np.int32)
    return audit_donation(
        _migration_scatter, (cache.cache, rows, jnp.asarray(page_ids))
    )


AUDITS = {
    "decode": _audit_decode,
    "verify": _audit_verify,
    "seat": _audit_seat,
    "seat_shared": _audit_seat_shared,
    "migration_scatter": _audit_migration_scatter,
}


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=KV_IDS)
@pytest.mark.parametrize("program", list(AUDITS))
def test_pool_programs_pass_the_donation_audit(
    model_and_params, program, kv_dtype
):
    model, params = model_and_params
    pool, report = AUDITS[program](model, params, kv_dtype)
    assert report.ok, report.describe()
    # Every leaf, scale rows too, and all of them in place.
    leaves = jax.tree.leaves(pool)
    assert report.num_deleted == report.num_leaves == len(leaves)
    assert len(leaves) == CFG.num_layers * (4 if kv_dtype else 2)
    assert report.reuse_frac == 1.0


def test_the_audit_tells_a_program_that_copies(model_and_params):
    """The audit's own negative case: the same decode, jitted as the
    parent jitted it."""
    model, params = model_and_params
    eng = _session(model, params).engine
    copying = jax.jit(paged_decode_fn(model, PAGE, False))
    _, report = _audit_decode_contract(copying, eng, params)
    assert not report.ok
    assert report.num_deleted == 0 and report.reuse_frac == 0.0


# ---------------------------------------------------------------------------
# the counter, session by session
# ---------------------------------------------------------------------------


def _lora_session(model, params, kv_dtype=None):
    from tpudl.models.lora import extract_adapters

    lp = LlamaForCausalLM(dataclasses.replace(CFG, lora_rank=2)).init(
        jax.random.key(3), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    tree = {
        path: {k: np.asarray(v) for k, v in f.items()}
        for path, f in extract_adapters(lp).items()
    }
    return _session(model, params, kv_dtype=kv_dtype, adapters={"t0": tree})


def _artifact_session(model, params, kv_dtype=None):
    from tpudl.export.decode import export_serving_decoder

    pre, dec = export_serving_decoder(
        model, params, SLOTS, PROMPT_LEN, page_size=PAGE,
        kv_dtype=kv_dtype,
    )
    return ServeSession.from_artifacts(pre, dec, params)


def _mesh_session(model, params, kv_dtype=None):
    from tpudl.fleet import build_mesh_session

    return build_mesh_session(
        model, params, PROMPT_LEN, devices=jax.devices()[:2], tp=2,
        num_slots=SLOTS, page_size=PAGE, kv_dtype=kv_dtype,
    )


SESSIONS = {
    "plain": lambda m, p: _session(m, p),
    "int8": lambda m, p: _session(m, p, kv_dtype="int8"),
    "prefix_share": lambda m, p: _session(m, p, prefix_share=True),
    "prefix_share_int8": lambda m, p: _session(
        m, p, prefix_share=True, kv_dtype="int8"
    ),
    "speculation": lambda m, p: _session(m, p, spec_k=2),
    "tenant_lora": _lora_session,
    "artifacts": _artifact_session,
    "artifacts_int8": lambda m, p: _artifact_session(m, p, "int8"),
    "mesh_tp2": _mesh_session,
    "mesh_tp2_int8": lambda m, p: _mesh_session(m, p, "int8"),
}


@pytest.mark.parametrize("kind", list(SESSIONS))
def test_serving_counts_no_pool_copy(model_and_params, kind):
    model, params = model_and_params
    registry().reset()
    session = SESSIONS[kind](model, params)
    eng = session.engine
    kept = [eng.cache.cache]
    if eng.speculator is not None:
        kept.append(eng.speculator.cache.cache)
    requests = _requests(5, shared=8 if "prefix" in kind else 0)
    if kind == "tenant_lora":
        requests[1].tenant = requests[3].tenant = "t0"
    results = session.serve(requests)
    assert all(results[r.request_id].finish_reason == "length"
               for r in requests)
    assert registry().counter("serve_decode_steps").value > 0
    # The series exists and reads 0, and what was kept is dead: a
    # second pool was never there to keep.
    assert "serve_kv_pool_copies" in registry().snapshot()["counters"]
    assert _copies() == 0
    for tree in kept:
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(tree))
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(jax.tree.leaves(kept[0])[0])
    # The live tree is the cache's, whole and in the pool's shapes.
    live = jax.tree.leaves(eng.cache.cache)
    assert not any(leaf.is_deleted() for leaf in live)
    assert all(leaf.shape[0] == eng.cache.num_pages for leaf in live)


def test_mesh_pool_keeps_its_sharding_and_compiles_once(model_and_params):
    """A sharded pool donates like a whole one: committed to the mesh
    before the first program, it comes back in the sharding it went in
    with, so nothing compiles twice."""
    model, params = model_and_params
    registry().reset()
    session = _mesh_session(model, params)
    cache = session.engine.cache
    before = jax.tree.leaves(cache.cache)[0].sharding
    assert before.spec == jax.sharding.PartitionSpec(None, None, "tp")
    session.serve(_requests(4))
    after = jax.tree.leaves(cache.cache)[0].sharding
    assert after.is_equivalent_to(before, 4)
    assert session.engine.decode_call._cache_size() == 1
    assert [fn._cache_size() for fn in cache._seat_jit.values()] == [1]
    assert _copies() == 0


def test_migration_counts_no_pool_copy(model_and_params):
    model, params = model_and_params
    registry().reset()
    src = _session(model, params)
    dst = _session(model, params)
    req = _requests(1)[0]
    src.submit(req)
    src.engine.step()
    src.engine.step()
    # The gather reads the live pool, after the last donating call.
    payload = src.engine.export_request(req.request_id)
    kept = dst.engine.cache.cache
    dst.engine.install_migrated(payload)
    assert jax.tree.leaves(kept)[0].is_deleted()
    results = dst.engine.run_until_drained()
    assert len(results[req.request_id].tokens) == req.max_new_tokens
    assert _copies() == 0


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=KV_IDS)
def test_a_program_without_donation_is_counted(model_and_params, kv_dtype):
    """Withhold donation (the parent's decode) and every decode step is
    a counted copy; the seats, which still donate, are not."""
    model, params = model_and_params
    registry().reset()
    session = _session(model, params, kv_dtype=kv_dtype)
    session.engine.decode_call = jax.jit(
        paged_decode_fn(model, PAGE, kv_dtype == "int8")
    )
    kept = session.engine.cache.cache
    session.serve(_requests(3))
    steps = registry().counter("serve_decode_steps").value
    assert steps > 0 and _copies() == steps
    # The first seat donated the tree kept from before the run; a tree
    # kept from before a copying decode would have survived it.
    assert jax.tree.leaves(kept)[0].is_deleted()


# ---------------------------------------------------------------------------
# the same tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=KV_IDS)
@pytest.mark.parametrize("seed", [0, 1])
def test_donation_changes_no_token(model_and_params, kv_dtype, seed):
    """Same seed, same programs, with and without donation (the parent
    commit's jit sites): the same tokens, request by request."""
    model, params = model_and_params
    donating = _session(model, params, kv_dtype=kv_dtype)
    template = jax.eval_shape(
        prefill_fn(model), params,
        jax.ShapeDtypeStruct((SLOTS, PROMPT_LEN), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS, PROMPT_LEN), jnp.int32),
    )[1]
    cache = PagedKVCache(template, page_size=PAGE, kv_dtype=kv_dtype)
    # The parent's seat as well: the same scatter, not donated.
    cache._seat_program = _copying_seat(cache)
    copying = ServeSession(
        jax.jit(prefill_fn(model)),
        jax.jit(paged_decode_fn(model, PAGE, kv_dtype == "int8")),
        params, cache, PROMPT_LEN,
    )
    registry().reset()
    want = copying.serve(_requests(5, seed=seed))
    assert _copies() > registry().counter("serve_decode_steps").value
    got = donating.serve(_requests(5, seed=seed))
    for rid, res in want.items():
        assert got[rid].tokens == res.tokens
        assert got[rid].finish_reason == res.finish_reason


def _copying_seat(cache):
    """``PagedKVCache._seat_program`` as the parent commit had it: the
    same scatter, jitted without donation."""
    programs = {}

    def seat_program(prompt_pages):
        if prompt_pages not in programs:
            programs[prompt_pages] = jax.jit(
                cache._make_seat_fn(prompt_pages)
            )
        return programs[prompt_pages]

    return seat_program
