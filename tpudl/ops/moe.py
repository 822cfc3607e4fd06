"""Mixture-of-experts FFN with expert parallelism over the ``ep`` mesh axis.

The reference lineage has no MoE (SURVEY.md §2.3 marks expert parallelism
absent from the reference tree); this makes sparse scaling first-class the
TPU way: routing is dense one-hot einsum algebra (GShard/Switch style) —
no gather/scatter, no dynamic shapes — so the dispatch/combine contractions
lower onto the MXU, and with expert weights sharded ``P('ep', ...)`` and
tokens sharded over (dp, fsdp), GSPMD inserts the all-to-all that moves
token blocks to their experts over ICI.

Routing math (top-k, capacity-bounded):
- router probs p = softmax(x @ w_r) in f32;
- k choices peeled off iteratively (argmax, mask, renormalize) with
  earlier choices taking dispatch priority;
- position_in_expert via cumsum over the token axis; tokens past an
  expert's capacity ``C = ceil(k * S * capacity_factor / E)`` are dropped
  (their combine weight is zero — the residual connection around the MoE
  layer carries them through unchanged);
- gate values normalized by the FULL top-k gate sum (GShard-style):
  combine weights sum to 1 only when all k choices were kept; a dropped
  choice's mass shrinks the survivors' weights rather than being
  reassigned to them;
- Switch-style load-balance aux loss ``E * sum_e f_e * p_e`` (f = top-1
  dispatch fraction, p = mean router prob), sown into the
  ``intermediates`` collection as ``moe_aux_loss`` for the train loop to
  pick up (tpudl.train.loop.make_classification_train_step
  ``moe_aux_weight``).
"""

from __future__ import annotations

import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpudl.ops.grouped_matmul import (
    grouped_kernel_ok,
    grouped_matmul,
    sum_choices,
)
from tpudl.parallel.sharding import constrain

P = jax.sharding.PartitionSpec

#: Sharding rules for MoE parameters, composable ahead of FSDP/TP rules:
#: expert dim over ep, then the usual megatron column/row split.
EP_MOE_RULES = (
    (r"(^|/)router/kernel$", P(None, None)),
    (r"(^|/)(wi|wg)$", P("ep", "fsdp", "tp")),
    (r"(^|/)wo$", P("ep", "tp", "fsdp")),
)


def with_moe_rules(base) -> tuple:
    """Prepend the MoE expert rules to a base rule list (first match wins,
    so expert params resolve before the generic kernel rules)."""
    return tuple(EP_MOE_RULES) + tuple(base or ())


def expert_capacity(
    seq_len: int, num_experts: int, k: int, capacity_factor: float
) -> int:
    return max(1, math.ceil(k * seq_len * capacity_factor / num_experts))


def route_topk(probs: jax.Array, k: int, capacity: int):
    """Build dispatch/combine tensors from router probabilities.

    probs: [G, S, E] f32 (softmax over E). Returns
    ``(dispatch [G,S,E,C] bool-ish f32, combine [G,S,E,C] f32, aux f32)``.
    """
    g, s, e = probs.shape
    top1_mask = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=probs.dtype)

    remaining = probs
    counts = jnp.zeros((g, 1, e), probs.dtype)
    dispatch = jnp.zeros((g, s, e, capacity), probs.dtype)
    gate_total = jnp.zeros((g, s), probs.dtype)
    combine = jnp.zeros((g, s, e, capacity), probs.dtype)

    for _ in range(k):
        idx = jnp.argmax(remaining, -1)  # [G, S]
        gate = jnp.max(remaining, -1)  # [G, S]
        mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)  # [G, S, E]
        # 0-based slot of each token within its expert, counting earlier
        # choices' kept assignments first (they have priority).
        pos = jnp.cumsum(mask, axis=1) - mask + counts  # [G, S, E]
        keep = (pos < capacity).astype(probs.dtype) * mask
        counts = counts + jnp.sum(keep, axis=1, keepdims=True)
        slot = jax.nn.one_hot(
            jnp.sum(pos * mask, -1).astype(jnp.int32), capacity,
            dtype=probs.dtype,
        )  # [G, S, C]
        disp = keep[..., None] * slot[:, :, None, :]  # [G, S, E, C]
        dispatch = dispatch + disp
        combine = combine + disp * gate[..., None, None]
        gate_total = gate_total + gate
        remaining = remaining * (1.0 - mask)

    # GShard/Switch normalization: divide by the sum of ALL top-k gates
    # (kept or not), so a token whose higher-probability expert was
    # capacity-dropped routes through its surviving choice with a
    # correspondingly SMALLER combine weight — the dropped mass falls to
    # the residual connection, it is not reassigned to the survivor.
    combine = combine / jnp.maximum(gate_total, 1e-9)[..., None, None]

    # Switch load-balance loss: E * sum_e (top-1 dispatch fraction) *
    # (mean router prob). 1.0 at perfect balance.
    f = jnp.mean(top1_mask, axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


class MoEMlp(nn.Module):
    """Expert-parallel FFN block (drop-in for a dense MLP of the same
    hidden/intermediate sizes; callers keep their residual connection, so
    capacity-dropped tokens pass through unchanged).

    ``gated=True`` gives the SwiGLU variant (Llama-style); otherwise a
    plain act(x@wi)@wo (BERT-style).
    """

    num_experts: int
    intermediate_size: int
    k: int = 2
    capacity_factor: float = 1.25
    gated: bool = False
    act: Callable = nn.gelu
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, m = x.shape
        e, h = self.num_experts, self.intermediate_size
        cap = expert_capacity(s, e, self.k, self.capacity_factor)

        # Router in f32: small matmul, and routing decisions are
        # precision-sensitive.
        logits = nn.Dense(
            e,
            use_bias=False,
            dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02),
            name="router",
        )(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, aux = route_topk(probs, self.k, cap)
        self.sow("intermediates", "moe_aux_loss", aux)

        init = nn.initializers.normal(0.02)
        wi = self.param("wi", init, (e, m, h)).astype(self.dtype)
        wo = self.param("wo", init, (e, h, m)).astype(self.dtype)

        xin = jnp.einsum("gsec,gsm->egcm", dispatch.astype(self.dtype), x)
        xin = constrain(xin, "ep", ("dp", "fsdp"), None, None)
        hh = jnp.einsum("egcm,emh->egch", xin, wi)
        if self.gated:
            wg = self.param("wg", init, (e, m, h)).astype(self.dtype)
            hh = self.act(hh) * jnp.einsum("egcm,emh->egch", xin, wg)
        else:
            hh = self.act(hh)
        out = jnp.einsum("egch,ehm->egcm", hh, wo)
        out = constrain(out, "ep", ("dp", "fsdp"), None, None)
        y = jnp.einsum("gsec,egcm->gsm", combine.astype(self.dtype), out)
        return y


# ---------------------------------------------------------------------------
# Dropless routed experts, one share of an expert-parallel deployment
# ---------------------------------------------------------------------------


class _ExpertKernel(nn.Module):
    """The stacked kernel ``[experts, in, out]`` of one projection of
    the experts held, under the path ``<name>/kernel`` that a dense
    projection has, so that tpudl.quant's rules address it. Like
    ``QuantDense`` it serves what the tree holds: a plain kernel, or
    the ``{"qvalues", "qscale"}`` pair of a quantized one (one scale
    per output channel, applied after the contraction)."""

    shape: tuple
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self):
        from tpudl.quant.quantize import is_quantized

        stored = (
            self.get_variable("params", "kernel")
            if self.has_variable("params", "kernel") else None
        )
        if is_quantized(stored):
            return stored["qvalues"].astype(self.dtype), stored["qscale"]
        kernel = self.param(
            "kernel", nn.initializers.normal(0.02), self.shape
        )
        return kernel.astype(self.dtype), None


#: The statistics a ``DroplessMoE`` sows into ``moe_stats``, in the
#: order the serving contracts return them (tpudl.models.generate).
MOE_STAT_NAMES = ("tokens_per_expert", "real_experts_a_token")

#: How a router turns its outputs into scores.
_SCORING = {
    "sigmoid": jax.nn.sigmoid,
    "softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
}

#: Rows of a traced program past which the experts are dispatched by
#: sorted groups (``DroplessMoE``): about four passes of the weights
#: through the dense form on a v5e.
SORTED_DISPATCH_ROWS = 1024


class DroplessMoE(nn.Module):
    """Routed SwiGLU experts with no capacity and no dropped token, as
    serving needs them (a dropped token changes the served logits), for
    the experts THIS program holds.

    The router keeps its published width and scores in float32. The
    selection bias ``b`` decides the choice and never the weight. Two
    routers, by ``scoring`` and ``renormalize``, ``f`` the
    ``routed_scaling_factor``:

        sigmoid, renormalised         softmax, not renormalised
        s = sigmoid(x W_r)            p = softmax(x W_r)
        T = top_k(s + b)              T = top_k(p + b)
        g_i = f s_i / sum_{j in T} s_j      g_i = f p_i

    (the two settings are independent; these are the pairs the served
    configurations publish). ``W_r`` has ``num_experts +
    zero_experts`` outputs. The ids past ``num_experts`` are IDENTITY
    experts: they return their input, hold no weights, reach no
    dispatch and no matmul, and in a deployment no exchange. The layer
    is told ``experts_held = (first, count)`` and computes

        y = sum_{i in T, first <= i < first + count} g_i E_i(x)
            + (sum_{i in T, i >= num_experts} g_i) x  +  E_shared(x)

    What the other experts would add is left out: in a deployment it
    arrives from the chips that hold them, and no code here stands in
    for them. The identity term (scope ``zero_experts``) and the shared
    expert are computed once, by every share for its own tokens; a
    token uses between 0 and ``experts_per_token`` real experts.

    Two dispatch forms compute the same sum over the same parameter
    tree, and the layer takes one by the STATIC row count of the
    program it is traced in (``dispatch="auto"``; no knob reaches it):

    - ``"dense"``: a ``[tokens, held]`` matrix of gates and the experts
      run as one grouped matmul over the experts held (``emh``): every
      held expert's weights pass the matrix unit once a call, which is
      what a call costs while an expert sees fewer tokens than the unit
      has rows; the gates fold into the down-projection's contraction,
      so no ``[held, tokens, hidden]`` tensor is made. Its operations
      grow with rows x held experts. An identity id has no column.
    - ``"sorted"``: the ``tokens x experts_per_token`` assignments are
      sorted by expert, the rows gathered in that order, and the three
      projections run as ragged grouped matmuls over the sorted groups:
      each row meets only the experts it chose. On one TPU device, for
      unquantized bfloat16 experts of whole-lane widths, they are the
      Pallas kernel of tpudl.ops.grouped_matmul (``grouped_kernel_ok``
      decides from what the traced program can observe; counter
      ``serve_moe_grouped_kernel``), else ``jax.lax.ragged_dot``: the
      same arithmetic. A choice of an expert held elsewhere, or of an
      identity expert, sorts behind the last group and is left out. The
      results go back to token order INSIDE the down projection's
      kernel call, which copies each row a group covers to its
      assignment's place ``token * k + choice`` of a float32
      ``[T * k, 1, m]`` result wherever the kernel is taken (counter
      ``serve_moe_rows_by_index``), else through the sort's inverse (a
      scatter of an iota) and one gather; a token's ``k`` rows are then
      summed in float32 under a select by the ``[T, k]`` ``held``
      (``sum_choices``, in the order the chip's reduce has always
      taken), and rounded once. A row held elsewhere is never written
      and never selected: no ``[T * k, m]`` tensor is zeroed, and with
      the kernel none is gathered.

    The dense form does ``rows`` operations for every byte of weights
    it streams, so it costs one pass of the weights up to about 240
    rows on a v5e (197 TFLOP/s over 819 GB/s); past
    ``SORTED_DISPATCH_ROWS``, a few such passes, the sorted form is
    taken. The counters ``serve_moe_dispatch_dense`` /
    ``serve_moe_dispatch_sorted`` count the layers traced each way.

    ``real`` ([B, S] bool) marks the tokens that count (not padding,
    not an idle slot); the int32 ``[held]`` count of real tokens per
    held expert is sown as ``moe_stats/tokens_per_expert``, and where
    the layer has identity experts the int32 ``[experts_per_token +
    1]`` count of real tokens by how many REAL experts (held or not)
    they chose as ``moe_stats/real_experts_a_token``
    (``MOE_STAT_NAMES``)."""

    num_experts: int
    experts_per_token: int
    intermediate_size: int
    shared_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: Any = None
    dtype: Any = jnp.bfloat16
    weight_dtype: Any = None
    #: "sigmoid" or "softmax" over the router's outputs.
    scoring: str = "sigmoid"
    #: Whether the chosen scores are divided by their sum.
    renormalize: bool = True
    #: Identity experts: router ids ``num_experts ...``.
    zero_experts: int = 0
    #: "auto" (by the traced program's rows), or one form by name: the
    #: seam tests and measurements hold the two forms against each
    #: other through.
    dispatch: str = "auto"

    def _dense(self, features: int, name: str):
        kwargs = dict(
            use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.normal(0.02), name=name,
        )
        if self.weight_dtype is not None:
            from tpudl.quant.dense import QuantDense

            return QuantDense(features, **kwargs)
        return nn.Dense(features, **kwargs)

    @nn.compact
    def __call__(self, x: jax.Array, real: jax.Array) -> jax.Array:
        b, s, m = x.shape
        first, count = self.experts_held or (0, self.num_experts)
        if not (0 <= first and 0 < count <= self.num_experts - first):
            raise ValueError(
                f"experts_held {(first, count)} outside the router's "
                f"{self.num_experts} experts"
            )
        if self.scoring not in _SCORING:
            raise ValueError(
                f"scoring must be one of {sorted(_SCORING)}, got "
                f"{self.scoring!r}"
            )
        h = self.intermediate_size
        width = self.num_experts + self.zero_experts
        tokens = x.reshape(b * s, m)
        with jax.named_scope("moe"):
            with jax.named_scope("router"):
                scores = _SCORING[self.scoring](nn.Dense(
                    width, use_bias=False, dtype=jnp.float32,
                    kernel_init=nn.initializers.normal(0.02), name="router",
                )(tokens.astype(jnp.float32)))
                bias = self.param(
                    "router_bias", nn.initializers.zeros, (width,)
                )
                _, chosen = jax.lax.top_k(
                    scores + bias.astype(jnp.float32), self.experts_per_token
                )  # [T, k]
                picked = jnp.take_along_axis(scores, chosen, axis=-1)
                gates = self.routed_scaling_factor * picked
                if self.renormalize:
                    gates = gates / jnp.sum(picked, axis=-1, keepdims=True)
                dispatch = self.dispatch
                if dispatch == "auto":
                    dispatch = (
                        "sorted" if b * s > SORTED_DISPATCH_ROWS else "dense"
                    )
                from tpudl.obs import registry

                registry().counter(f"serve_moe_dispatch_{dispatch}").inc()
                if dispatch == "dense":
                    # [T, k, held]: which held expert each choice names.
                    hit = (chosen - first)[..., None] == jnp.arange(count)
                    combine = jnp.sum(hit * gates[..., None], axis=1)  # [T, held]
                    counts = jnp.sum(
                        hit.any(axis=1) & real.reshape(-1, 1), axis=0,
                        dtype=jnp.int32,
                    )
                else:
                    # No [T, k, held] tensor at these row counts: the
                    # held choices of the real tokens, counted by expert.
                    local = chosen - first
                    held = (local >= 0) & (local < count)
                    counts = jnp.zeros((count + 1,), jnp.int32).at[
                        jnp.where(held & real.reshape(-1, 1), local, count)
                    ].add(1)[:count]
                self.sow("moe_stats", "tokens_per_expert", counts)
                if self.zero_experts:
                    # Identity choices name no weights: their gates are
                    # summed a token, and the tokens counted by how
                    # many real experts they chose.
                    zero = chosen >= self.num_experts
                    zero_gate = jnp.sum(jnp.where(zero, gates, 0.0), axis=-1)
                    chose = (self.experts_per_token - zero.sum(axis=-1))[
                        :, None
                    ] == jnp.arange(self.experts_per_token + 1)
                    self.sow(
                        "moe_stats", "real_experts_a_token",
                        jnp.sum(
                            chose & real.reshape(-1, 1), axis=0,
                            dtype=jnp.int32,
                        ),
                    )
            with jax.named_scope("experts"):
                wg, sg = _ExpertKernel((count, m, h), self.dtype, name="gate_proj")()
                wu, su = _ExpertKernel((count, m, h), self.dtype, name="up_proj")()
                wd, sd = _ExpertKernel((count, h, m), self.dtype, name="down_proj")()
                if dispatch == "dense":
                    gate = jnp.einsum("tm,emh->eth", tokens, wg)
                    up = jnp.einsum("tm,emh->eth", tokens, wu)
                    weight = combine.T[..., None]  # [held, T, 1]
                else:
                    # Assignment a = token a // k, choice a % k. Sorted
                    # by the held expert it names; those held elsewhere
                    # go behind the last group, which no group covers.
                    k = self.experts_per_token
                    key = jnp.where(held, local, count).reshape(-1)
                    order = jnp.argsort(key)
                    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(
                        1
                    )[:count]
                    rows = tokens[order // k]  # [T * k, m]
                    grouped = jax.lax.ragged_dot
                    if grouped_kernel_ok(rows, (wg, wu, wd), (sg, su, sd)):
                        grouped = grouped_matmul
                        registry().counter("serve_moe_grouped_kernel").inc()
                    gate = grouped(rows, wg, sizes)
                    up = grouped(rows, wu, sizes)
                    weight = gates.reshape(-1)[order, None]  # [T * k, 1]
                if sg is not None:
                    gate = gate * sg.astype(gate.dtype)
                    up = up * su.astype(up.dtype)
                act = nn.silu(gate) * up
                act = act * weight.astype(act.dtype)
                if dispatch == "dense":
                    routed = jnp.einsum(
                        "eth,ehm->tm", act, wd,
                        preferred_element_type=jnp.float32,
                    )
                else:
                    if grouped is grouped_matmul:
                        # The kernel puts a sorted row's result at its
                        # assignment's place, [T * k, 1, m]; a place
                        # nobody wrote is selected away in the sum.
                        registry().counter("serve_moe_rows_by_index").inc()
                        out = grouped_matmul(
                            act, wd, sizes, jnp.float32, rows_to=order
                        )
                        routed = sum_choices(out, held)
                    else:
                        # Back to assignment order through the sort's
                        # inverse, a scatter of an iota; ``ragged_dot``
                        # left zeros behind the last group, and the
                        # select keeps the sum to the rows held.
                        inv = jnp.zeros_like(order).at[order].set(
                            jnp.arange(order.shape[0], dtype=order.dtype),
                            unique_indices=True,
                        )
                        out = jax.lax.ragged_dot(
                            act, wd, sizes, preferred_element_type=jnp.float32
                        )[inv].reshape(b * s, k, m)
                        routed = jnp.where(held[..., None], out, 0.0).sum(
                            axis=1
                        )
                if sd is not None:
                    routed = routed * sd
                routed = routed.astype(self.dtype)
            y = routed
            if self.zero_experts:
                with jax.named_scope("zero_experts"):
                    y = y + (
                        zero_gate[:, None] * tokens.astype(jnp.float32)
                    ).astype(self.dtype)
            if self.shared_intermediate_size:
                with jax.named_scope("shared_expert"):
                    f = self.shared_intermediate_size
                    sh = nn.silu(
                        self._dense(f, "shared_gate_proj")(tokens)
                    ) * self._dense(f, "shared_up_proj")(tokens)
                    y = y + self._dense(m, "shared_down_proj")(sh)
        return y.reshape(b, s, m)
