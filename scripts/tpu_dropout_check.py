"""Real-TPU checks for what the CPU test tier cannot cover.

1. Statistical checks of the in-kernel (PRNG-backed) dropout paths —
   the half of tpudl.ops.fused_attention / tpudl.ops.softmax_dropout /
   flash_attention that pallas interpret mode cannot emulate (no PRNG).
2. Compiled-kernel parity against the XLA composites at BERT-base and
   Llama-1B widths for the epilogue, cross-entropy, segmented-LoRA and
   masked fused-attention kernels: interpret mode checks their
   arithmetic, tests/test_tpu_compile.py that they compile, this that
   the compiled kernel computes the same thing.

Run on a machine with a TPU: python scripts/tpu_dropout_check.py
Prints PASS/FAIL per check; exits nonzero on failure, and without a TPU
(a pass that checked nothing is not a pass).
"""

import itertools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp

from tpudl.ops.attention import attend, is_tpu_backend
from tpudl.ops.fused_attention import fused_attention
from tpudl.ops.softmax_dropout import softmax_dropout


def _max_rel(a, b) -> float:
    """max|a - b| over max|b| in f32 (b the reference)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


def compiled_parity(check) -> None:
    """Compiled Pallas kernels vs their XLA composites, forward and
    backward, at the widths the models run them at. bf16 cases compare
    at bf16 resolution (2^-8 relative, a few ulps of headroom); f32
    cases at the f32 matmul/reduction floor."""
    from tpudl.ops.cross_entropy import (
        softmax_cross_entropy,
        softmax_cross_entropy_ref,
    )
    from tpudl.ops.mlp_fused import (
        bias_gelu,
        bias_gelu_ref,
        swiglu,
        swiglu_ref,
    )
    from tpudl.ops.norms import (
        layer_norm,
        layer_norm_ref,
        rms_norm,
        rms_norm_ref,
    )
    from tpudl.ops.segmented_lora import segmented_lora, segmented_lora_ref

    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = (jax.random.fold_in(jax.random.key(5), i) for i in itertools.count())

    def rnd(shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(next(ks), shape, f32) * scale).astype(dtype)

    def pair(name, fused_fn, ref_fn, args, tol, argnums):
        """Forward and gradient parity of one kernel against its
        composite on the same inputs."""
        coef = rnd(jax.eval_shape(ref_fn, *args).shape, f32)
        rel = _max_rel(fused_fn(*args), ref_fn(*args))
        check(f"{name} fwd (rel {rel:.2e})", rel < tol)
        loss = lambda fn: lambda *a: jnp.sum(fn(*a).astype(f32) * coef)
        gf = jax.grad(loss(fused_fn), argnums=argnums)(*args)
        gr = jax.grad(loss(ref_fn), argnums=argnums)(*args)
        for i, (a, b) in enumerate(zip(gf, gr)):
            rel = _max_rel(a, b)
            check(f"{name} grad[{i}] (rel {rel:.2e})", rel < tol)

    tol = 2e-2  # bf16 outputs/cotangents: a few 2^-8 ulps
    # BERT-base rows: 4096 x 768 / 3072 (a slice of b256 s128's 32768).
    x, r = rnd((4096, 768)), rnd((4096, 768))
    scale, bias = 1.0 + rnd((768,), f32, 0.1), rnd((768,), f32, 0.1)
    pair("layer_norm+residual [4096,768]",
         lambda x, r, s, b: layer_norm(x, s, b, r, return_sum=False,
                                       impl="fused"),
         lambda x, r, s, b: layer_norm_ref(x, s, b, r)[0],
         (x, r, scale, bias), tol, (0, 1, 2, 3))
    pair("bias_gelu [4096,3072]",
         lambda x, b: bias_gelu(x, b, impl="fused"), bias_gelu_ref,
         (rnd((4096, 3072)), rnd((3072,), f32, 0.5)), tol, (0, 1))
    # Llama-1B rows: decode (8) and prefill (4096) x 2048 / 8192.
    scale = 1.0 + rnd((2048,), f32, 0.1)
    for rows in (8, 4096):
        pair(f"rms_norm+residual [{rows},2048]",
             lambda x, r, s: rms_norm(x, s, r, return_sum=False,
                                      impl="fused"),
             lambda x, r, s: rms_norm_ref(x, s, r)[0],
             (rnd((rows, 2048)), rnd((rows, 2048)), scale), tol, (0, 1, 2))
        pair(f"swiglu [{rows},8192]",
             lambda g, u: swiglu(g, u, impl="fused"), swiglu_ref,
             (rnd((rows, 8192)), rnd((rows, 8192))), tol, (0, 1))
    for shape, dtype in (((256, 2), f32), ((512, 128256), bf16)):
        labels = jax.random.randint(next(ks), shape[:1], 0, shape[1])
        pair(f"cross_entropy {list(shape)} {jnp.dtype(dtype).name}",
             lambda z: softmax_cross_entropy(z, labels, impl="fused"),
             lambda z: softmax_cross_entropy_ref(z, labels),
             (rnd(shape, dtype, 3.0),), tol if dtype == bf16 else 1e-4,
             (0,))

    # Segmented LoRA, decode: 8 slots x rank<=8 over 64-page pools; the
    # reference einsums run at full f32 precision (the kernel's sums
    # are f32 on the VPU).
    table = jax.random.randint(next(ks), (8, 8), 0, 64).astype(jnp.int32)
    table = table.at[3].set(0).at[:, 6:].set(0)  # empty slot, short ranks
    slot_scale = jnp.abs(rnd((8,), f32)).at[3].set(0.0)
    for quant in (False, True):
        if quant:
            pools = {
                "a": jax.random.randint(next(ks), (64, 2048), -127, 128)
                .astype(jnp.int8).at[0].set(0),
                "b": jax.random.randint(next(ks), (64, 8192), -127, 128)
                .astype(jnp.int8).at[0].set(0),
                "a_scale": jnp.abs(rnd((64,), f32, 0.01)),
                "b_scale": jnp.abs(rnd((64,), f32, 0.01)),
            }
        else:
            pools = {"a": rnd((64, 2048), f32, 0.05).at[0].set(0.0),
                     "b": rnd((64, 8192), f32, 0.05).at[0].set(0.0)}
        xs = rnd((8, 2048))
        got = segmented_lora(xs, pools, table, slot_scale, impl="fused")
        with jax.default_matmul_precision("highest"):
            want = segmented_lora_ref(xs, pools, table, slot_scale)
        name = f"segmented_lora decode [8,2048]->8192 {'int8' if quant else 'f32'}"
        rel = _max_rel(got, want)
        check(f"{name} (rel {rel:.2e})", rel < tol)
        check(f"{name} empty slot is zero", bool(jnp.all(got[3] == 0)))

    # Fused attention with a kv-validity mask at its longest sequence.
    q, k, v = (rnd((4, 512, 12, 64)) for _ in range(3))
    mask = (jnp.arange(512)[None, :] < jnp.asarray([512, 400, 77, 1])[:, None])
    mask = mask.astype(jnp.int32)
    pair("fused_attention mask [4,512,12,64]",
         lambda q, k, v: fused_attention(q, k, v, mask=mask),
         lambda q, k, v: attend(q, k, v, mask),
         (q, k, v), tol, (0, 1, 2))


def main() -> int:
    if not is_tpu_backend():
        print("tpu_dropout_check needs a TPU: "
              f"found {jax.default_backend()!r}", file=sys.stderr)
        return 2
    failures = 0

    def check(name, ok):
        nonlocal failures
        print(f"{'PASS' if ok else 'FAIL'}: {name}")
        failures += 0 if ok else 1

    compiled_parity(check)

    B, S, H, D = 4, 128, 8, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    rng = jax.random.key(42)

    # Determinism: same key -> bit-identical outputs and grads.
    o1 = fused_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng)
    o2 = fused_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng)
    check("fused fwd deterministic per key", bool(jnp.all(o1 == o2)))
    o3 = fused_attention(q, k, v, dropout_rate=0.1,
                         dropout_rng=jax.random.key(43))
    check("fused fwd differs across keys", bool(jnp.any(o1 != o3)))
    g1 = jax.grad(lambda q: jnp.sum(
        fused_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng) ** 2
    ))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        fused_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng) ** 2
    ))(q)
    check("fused bwd deterministic per key", bool(jnp.all(g1 == g2)))
    check("fused bwd finite", bool(jnp.all(jnp.isfinite(g1))))

    # Expectation: mean over keys approaches the no-dropout output.
    base = attend(q, k, v)
    f = jax.jit(lambda r: fused_attention(
        q, k, v, dropout_rate=0.1, dropout_rng=r
    ))
    acc = jnp.zeros_like(base)
    n = 96
    for i in range(n):
        acc = acc + f(jax.random.key(100 + i))
    err = float(jnp.mean(jnp.abs(acc / n - base)))
    check(f"fused E[dropout out] ~ base (mean_abs {err:.4f})", err < 0.02)

    # softmax_dropout keep fraction via uniform probabilities.
    logits = jnp.zeros((2, 2, 128, 128))
    p = softmax_dropout(logits, dropout_rate=0.1,
                        dropout_rng=rng, out_dtype=jnp.float32)
    # each kept element is (1/S)/(1-r); fraction kept ~ 1 - r
    kept = float(jnp.mean((p > 0).astype(jnp.float32)))
    check(f"softmax_dropout keep fraction {kept:.4f} ~ 0.9",
          abs(kept - 0.9) < 0.01)

    # ---- flash attention in-kernel dropout (round-4) --------------------
    # The strong check: extract the kernel's effective post-dropout
    # attention weights by feeding v = I (D = Skv), rebuild the SAME
    # computation in plain XLA from the extracted keep-mask, and compare
    # output AND all three gradients. This verifies (a) the dropout math
    # (denominator undropped, numerator masked+rescaled), (b) the
    # fwd/bwd mask bit-consistency across the q-major and kv-major grids.
    from tpudl.ops.flash_attention import flash_attention

    Bf, Sf, Hf = 2, 256, 2  # D = Sf for the identity-V trick
    rate = 0.3
    kq, kk2 = jax.random.split(jax.random.key(7))
    qf = jax.random.normal(kq, (Bf, Sf, Hf, Sf), jnp.float32)
    kf = jax.random.normal(kk2, (Bf, Sf, Hf, Sf), jnp.float32)
    v_eye = jnp.broadcast_to(
        jnp.eye(Sf, dtype=jnp.float32)[:, None, :], (Sf, Hf, Sf)
    )[None].repeat(Bf, axis=0)
    frng = jax.random.key(11)
    # effective weights w' = keep * softmax / (1-rate), per (b, h)
    w_eff = flash_attention(
        qf, kf, v_eye, dropout_rate=rate, dropout_rng=frng,
        block_q=128, block_k=128,
    )  # [B, Sq, H, Skv]
    w_full = flash_attention(qf, kf, v_eye, block_q=128, block_k=128)
    keep_mask = (jnp.abs(w_eff) > 0).astype(jnp.float32)
    kept_frac = float(jnp.mean(keep_mask))
    check(f"flash dropout keep fraction {kept_frac:.4f} ~ {1 - rate}",
          abs(kept_frac - (1 - rate)) < 0.01)
    # extracted weights == undropped weights masked+rescaled
    w_ref = w_full * keep_mask / (1 - rate)
    werr = float(jnp.max(jnp.abs(w_eff - w_ref)))
    check(f"flash dropout = mask(softmax)/(1-r) (max_abs {werr:.2e})",
          werr < 3e-5)
    # fwd-vs-bwd mask bit-equality: vjp with identity cotangent returns
    # dv[b,k,h,j] = w'_bwd[b,j,h,k] — the BACKWARD pass's effective
    # weights. The kv-major dk/dv grid must regenerate the exact keep
    # pattern the q-major forward drew.
    _, vjp_fn = jax.vjp(
        lambda v_: flash_attention(
            qf, kf, v_, dropout_rate=rate, dropout_rng=frng,
            block_q=128, block_k=128,
        ),
        v_eye,
    )
    w_bwd = jnp.transpose(vjp_fn(v_eye)[0], (0, 3, 2, 1))
    mask_mismatch = int(jnp.sum((w_eff > 0) != (w_bwd > 0)))
    check(f"flash fwd/bwd dropout masks bit-identical "
          f"({mask_mismatch} mismatches)", mask_mismatch == 0)

    # Gradient parity vs the XLA reconstruction with the SAME mask. The
    # keep-mask depends only on (rng, rate, grid geometry) — not on
    # q/k/v values or head_dim — so the mask extracted above (D=Sf for
    # the identity trick) applies verbatim to these D=64 tensors as long
    # as B/H/S/blocks match.
    qs = jax.random.normal(jax.random.key(8), (Bf, Sf, Hf, 64), jnp.float32)
    ks_ = jax.random.normal(jax.random.key(9), (Bf, Sf, Hf, 64), jnp.float32)
    kv3 = jax.random.normal(jax.random.key(10), (Bf, Sf, Hf, 64), jnp.float32)
    scale = qs.shape[-1] ** -0.5

    def ref_fn(q_, k_, v_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * scale
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        wk = w * jnp.transpose(keep_mask, (0, 2, 1, 3)) / (1 - rate)
        return jnp.einsum("bhqk,bkhd->bqhd", wk, v_)

    def flash_fn(q_, k_, v_):
        return flash_attention(
            q_, k_, v_, dropout_rate=rate, dropout_rng=frng,
            block_q=128, block_k=128,
        )

    def ref_plain(q_, k_, v_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * scale
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v_)

    def flash_plain(q_, k_, v_):
        return flash_attention(q_, k_, v_, block_q=128, block_k=128)

    gcoef = jax.random.normal(jax.random.key(13), (Bf, Sf, Hf, 64))
    gr = jax.grad(lambda args: jnp.sum(ref_fn(*args) * gcoef))((qs, ks_, kv3))
    gf = jax.grad(lambda args: jnp.sum(flash_fn(*args) * gcoef))((qs, ks_, kv3))
    # Calibrate against the NO-dropout kernel's own numerical floor vs
    # XLA (TPU f32 matmul passes + online-softmax reassociation measure
    # ~1.2-1.6e-3 rel here): dropout grads must sit within 3x of it —
    # a wrong/new mask in the backward shows up orders of magnitude
    # larger (fwd-vs-bwd mask equality is separately asserted exactly by
    # the w'-extraction check above).
    g0r = jax.grad(lambda args: jnp.sum(ref_plain(*args) * gcoef))((qs, ks_, kv3))
    g0f = jax.grad(lambda args: jnp.sum(flash_plain(*args) * gcoef))((qs, ks_, kv3))
    names = ("dq", "dk", "dv")
    for name, a, b2, a0, b0 in zip(names, gr, gf, g0r, g0f):
        rel = float(jnp.max(jnp.abs(a - b2))) / (
            float(jnp.max(jnp.abs(a))) + 1e-9
        )
        base_rel = float(jnp.max(jnp.abs(a0 - b0))) / (
            float(jnp.max(jnp.abs(a0))) + 1e-9
        )
        check(
            f"flash dropout {name} parity (rel {rel:.2e}, no-dropout "
            f"floor {base_rel:.2e})",
            rel < max(3 * base_rel, 1e-4),
        )

    # determinism per key, variation across keys, causal+mask composition
    o1 = flash_fn(qs, ks_, kv3)
    o2 = flash_fn(qs, ks_, kv3)
    check("flash dropout fwd deterministic per key", bool(jnp.all(o1 == o2)))
    o3 = flash_attention(qs, ks_, kv3, dropout_rate=rate,
                         dropout_rng=jax.random.key(12),
                         block_q=128, block_k=128)
    check("flash dropout differs across keys", bool(jnp.any(o1 != o3)))
    padmask = (jnp.arange(Sf)[None, :] < Sf - 17).astype(jnp.int32)
    padmask = jnp.broadcast_to(padmask, (Bf, Sf))
    oc = flash_attention(qs, ks_, kv3, mask=padmask, causal=True,
                         dropout_rate=rate, dropout_rng=frng)
    check("flash dropout + causal + padding finite",
          bool(jnp.all(jnp.isfinite(oc))))
    # attend() long-context dispatch: fused impl beyond MAX_SEQ routes to
    # flash WITH dropout (the removed round-3 carve-out)
    S_long = 2048
    q4 = jax.random.normal(jax.random.key(20), (1, S_long, 2, 64), jnp.bfloat16)
    k4 = jax.random.normal(jax.random.key(21), (1, S_long, 2, 64), jnp.bfloat16)
    v4 = jax.random.normal(jax.random.key(22), (1, S_long, 2, 64), jnp.bfloat16)
    o_long = attend(q4, k4, v4, implementation="fused", causal=True,
                    dropout_rate=0.1, dropout_rng=frng)
    check("attend seq-2048 dropout via flash finite",
          bool(jnp.all(jnp.isfinite(o_long.astype(jnp.float32)))))

    # ulysses dropout with the FLASH local body on the real chip (the CPU
    # tier covers local_impl='reference'): single-device degenerate path
    # (no mesh on one chip) must be deterministic per key and match the
    # expectation of the base output.
    from tpudl.ops.ulysses import ulysses_attention

    qs2 = jax.random.normal(jax.random.key(30), (2, 256, 4, 64), jnp.float32)
    ks2 = jax.random.normal(jax.random.key(31), (2, 256, 4, 64), jnp.float32)
    vs2 = jax.random.normal(jax.random.key(32), (2, 256, 4, 64), jnp.float32)
    u1 = ulysses_attention(qs2, ks2, vs2, local_impl="flash",
                           dropout_rate=0.2, dropout_rng=frng)
    u2 = ulysses_attention(qs2, ks2, vs2, local_impl="flash",
                           dropout_rate=0.2, dropout_rng=frng)
    check("ulysses flash dropout deterministic per key",
          bool(jnp.all(u1 == u2)))
    ubase = ulysses_attention(qs2, ks2, vs2, local_impl="flash")
    uf = jax.jit(lambda r: ulysses_attention(
        qs2, ks2, vs2, local_impl="flash", dropout_rate=0.2, dropout_rng=r
    ))
    uacc = jnp.zeros_like(ubase)
    un = 64
    for i in range(un):
        uacc = uacc + uf(jax.random.key(300 + i))
    uerr = float(jnp.mean(jnp.abs(uacc / un - ubase)))
    check(f"ulysses flash E[dropout out] ~ base (mean_abs {uerr:.4f})",
          uerr < 0.05)

    # ring attention with the FLASH tick body (round 5): per-tick
    # (o, lse) merge with in-kernel dropout whose lse is of the
    # UNDROPPED distribution — deterministic per key, expectation
    # matching the undropped output, on a real sp mesh shape (sp=1 on
    # one chip exercises the shard_map + kernel path end to end).
    from tpudl.ops.ring_attention import ring_attention
    from tpudl.runtime.mesh import MeshSpec, make_mesh

    # Wildcard dp: the mesh fits any device count (the script's
    # run-anywhere contract); sp stays 1 so the ring body is the
    # single-shard degenerate that still runs shard_map + the kernel.
    rmesh = make_mesh(MeshSpec(dp=-1, sp=1))
    r1 = ring_attention(qs2, ks2, vs2, causal=True, mesh=rmesh,
                        local_impl="flash", dropout_rate=0.2,
                        dropout_rng=frng)
    r2 = ring_attention(qs2, ks2, vs2, causal=True, mesh=rmesh,
                        local_impl="flash", dropout_rate=0.2,
                        dropout_rng=frng)
    check("ring flash dropout deterministic per key",
          bool(jnp.all(r1 == r2)))
    rbase = ring_attention(qs2, ks2, vs2, causal=True, mesh=rmesh,
                           local_impl="flash")
    rf = jax.jit(lambda r: ring_attention(
        qs2, ks2, vs2, causal=True, mesh=rmesh, local_impl="flash",
        dropout_rate=0.2, dropout_rng=r,
    ))
    racc = jnp.zeros_like(rbase)
    for i in range(un):
        racc = racc + rf(jax.random.key(400 + i))
    rerr = float(jnp.mean(jnp.abs(racc / un - rbase)))
    check(f"ring flash E[dropout out] ~ base (mean_abs {rerr:.4f})",
          rerr < 0.05)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
