"""Decode-path serving export (tpudl.export.decode).

The reference's substance is exported-artifact inference (reference
notebooks/cv/onnx_experiments.py:33-42,77-140: export -> session ->
run + parity); this is the decoder analog: serialize prefill + decode
with the KV cache as explicit I/O, deserialize, and reproduce live
generate() token for token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.export.decode import (
    decode_fn,
    export_decoder,
    generate_with_exported,
    load_decoder,
    prefill_fn,
)
from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=64)
B, S, NEW = 2, 8, 12


def _setup():
    model = LlamaForCausalLM(CFG)
    ids = jnp.asarray(
        np.random.default_rng(3).integers(5, 500, size=(B, S)), jnp.int32
    )
    params = model.init(jax.random.key(0), ids)["params"]
    return model, params, ids


def test_functional_prefill_decode_match_live_generate():
    """The pure-function (explicit-cache) forms reproduce the flax
    mutable-state decode exactly, pre-serialization."""
    model, params, ids = _setup()
    want = generate(model, params, ids, max_new_tokens=NEW)
    pf, df = prefill_fn(model), decode_fn(model)
    logits, cache = jax.jit(pf)(params, ids, jnp.ones_like(ids))
    token = jnp.argmax(logits, -1).astype(jnp.int32)
    position = jnp.full((B,), S, jnp.int32)
    toks = [token]
    dstep = jax.jit(df)
    for _ in range(NEW - 1):
        logits, cache = dstep(params, cache, token, position)
        position = position + 1
        token = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(token)
    np.testing.assert_array_equal(
        np.asarray(jnp.stack(toks, 1)), np.asarray(want)
    )


def test_exported_roundtrip_reproduces_generate(tmp_path):
    """Serialize -> deserialize -> generate: token-identical to the live
    model, through files on disk (the full reference loop)."""
    model, params, ids = _setup()
    prefix = str(tmp_path / "llama_tiny")
    export_decoder(model, params, B, S, path_prefix=prefix)
    prefill_call, decode_call = load_decoder(
        f"{prefix}.prefill.stablehlo", f"{prefix}.decode.stablehlo"
    )
    got = generate_with_exported(
        prefill_call, decode_call, params, ids, max_new_tokens=NEW,
        max_seq_len=CFG.max_seq_len,
    )
    want = generate(model, params, ids, max_new_tokens=NEW)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The serving loop enforces the exporting model's KV-cache bound —
    # the deserialized callables cannot see it themselves.
    import pytest

    with pytest.raises(ValueError, match="max_seq_len"):
        generate_with_exported(
            prefill_call, decode_call, params, ids,
            max_new_tokens=CFG.max_seq_len, max_seq_len=CFG.max_seq_len,
        )


def test_exported_ragged_padded_batch(tmp_path):
    """The exported artifacts serve LEFT-padded ragged batches: the
    cache's per-slot validity travels as explicit I/O, so each padded
    row reproduces its unpadded generation token for token — the moment
    'a second input arrives' the serving path still answers correctly."""
    model, params, ids = _setup()
    pre, dec = export_decoder(model, params, B, S)
    prefill_call, decode_call = load_decoder(pre, dec)
    # Row 0: full-length prompt; row 1: 5 real tokens, left-padded by 3.
    short = ids[1:2, 3:]
    mask = jnp.concatenate(
        [
            jnp.ones((1, S), jnp.int32),
            jnp.concatenate(
                [jnp.zeros((1, 3), jnp.int32), jnp.ones((1, S - 3), jnp.int32)],
                axis=1,
            ),
        ],
        axis=0,
    )
    ragged_ids = jnp.concatenate(
        [ids[0:1], jnp.concatenate([jnp.zeros((1, 3), jnp.int32), short], 1)],
        axis=0,
    )
    got = generate_with_exported(
        prefill_call, decode_call, params, ragged_ids,
        attention_mask=mask, max_new_tokens=NEW, max_seq_len=CFG.max_seq_len,
    )
    want0 = generate(model, params, ids[0:1], max_new_tokens=NEW)
    want1 = generate(model, params, short, max_new_tokens=NEW)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want0[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want1[0]))
    import pytest

    with pytest.raises(ValueError, match="LEFT-padded"):
        generate_with_exported(
            prefill_call, decode_call, params, ragged_ids,
            attention_mask=mask[:, ::-1], max_new_tokens=2,
        )


def test_exported_eos_padding():
    model, params, ids = _setup()
    pre, dec = export_decoder(model, params, B, S)
    prefill_call, decode_call = load_decoder(pre, dec)
    # Force an eos that WILL be produced: run once, take the first
    # generated token of row 0 as the eos id.
    first = generate_with_exported(
        prefill_call, decode_call, params, ids, max_new_tokens=3
    )
    eos = int(first[0, 0])
    got = generate_with_exported(
        prefill_call, decode_call, params, ids, max_new_tokens=5, eos_id=eos
    )
    row = np.asarray(got)[0]
    assert row[0] == eos and np.all(row == eos)  # padded after first eos


def test_exported_early_exit_skips_decode_calls():
    """Regression: the exported serving loop early-exits when every row
    is done — a batch finishing at token 1 used to pay max_new_tokens-1
    dead decode dispatches; now it pays zero and eos-pads the output."""
    model, params, ids = _setup()
    # Batch-1 artifacts so "every row done at token 1" is constructible
    # (one row's first token IS the eos).
    pre, dec = export_decoder(model, params, 1, S)
    prefill_call, decode_call = load_decoder(pre, dec)

    calls = []

    def counting_decode(*args):
        calls.append(1)
        return decode_call(*args)

    first = generate_with_exported(
        prefill_call, decode_call, params, ids[0:1], max_new_tokens=1
    )
    eos_row0 = int(first[0, 0])
    calls.clear()
    got = generate_with_exported(
        prefill_call, counting_decode, params, ids[0:1],
        max_new_tokens=10, eos_id=eos_row0,
    )
    assert len(calls) == 0, (
        f"all-done batch ran {len(calls)} dead decode calls"
    )
    row = np.asarray(got)[0]
    assert row.shape == (10,) and np.all(row == eos_row0)

    # A live row must NOT trigger the early exit: pick an eos the row
    # does not emit in 6 tokens — every decode dispatch still happens.
    calls.clear()
    probe = np.asarray(
        generate_with_exported(
            prefill_call, decode_call, params, ids[0:1], max_new_tokens=6
        )
    )[0]
    never_eos = int(
        next(t for t in range(CFG.vocab_size) if t not in set(probe))
    )
    got2 = generate_with_exported(
        prefill_call, counting_decode, params, ids[0:1],
        max_new_tokens=6, eos_id=never_eos,
    )
    assert np.asarray(got2).shape == (1, 6)
    assert len(calls) == 5  # max_new_tokens - 1, no dead skipping

    # The readback is PACED: a mid-stream finish is only noticed at the
    # next eos_check_every boundary (per-token host syncs would
    # serialize the async dispatch pipeline), and the overshoot rows are
    # eos anyway, so outputs are unchanged.
    hit = 3
    eos_mid = int(probe[hit])
    hit = int(np.argmax(probe == eos_mid))  # first occurrence
    calls.clear()
    got3 = generate_with_exported(
        prefill_call, counting_decode, params, ids[0:1],
        max_new_tokens=12, eos_id=eos_mid, eos_check_every=1,
    )
    assert len(calls) == hit  # per-token checks: exit the step eos lands
    row3 = np.asarray(got3)[0]
    assert row3[hit] == eos_mid and np.all(row3[hit:] == eos_mid)
    import pytest

    with pytest.raises(ValueError, match="eos_check_every"):
        generate_with_exported(
            prefill_call, decode_call, params, ids[0:1],
            max_new_tokens=2, eos_id=0, eos_check_every=0,
        )


def test_decode_latency_harness_runs():
    """The latency harness (warmup-excluded, transfer/compute split)
    accepts the exported decode step — the reference's latency loop
    (onnx_experiments.py:90-104) applied to serving decode."""
    from tpudl.export.latency import latency_benchmark

    model, params, ids = _setup()
    pf = prefill_fn(model)
    _, cache = jax.jit(pf)(params, ids, jnp.ones_like(ids))
    token = jnp.zeros((B,), jnp.int32)
    position = jnp.full((B,), S, jnp.int32)
    out = latency_benchmark(
        decode_fn(model), (params, cache, token, position),
        warmup=1, iters=3,
    )
    assert out["compute"]["mean_ms"] > 0
    assert out["transfer"]["mean_ms"] > 0
    # Tail percentiles ride alongside the legacy keys (serving SLOs are
    # quoted at p99), and the warmup count is part of the record.
    for window in ("compute", "transfer"):
        stats = out[window]
        assert stats["p99_ms"] >= stats["p95_ms"] >= stats["p50_ms"]
        assert stats["max_ms"] >= stats["p99_ms"]
        assert stats["min_ms"] <= stats["p50_ms"]
    assert out["warmup"] == 1
