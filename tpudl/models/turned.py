"""Kernels held the way round the serving programs read them.

A flax kernel is declared ``[in, out]`` and lies on the chip as its
shape says, ``out`` minor. The TPU compiler reads the attention
projections whose output is split into heads the other way round: the
fusion that folds the norm, the projection and the reshape to heads
wants ``in`` MINOR, and handed ``[in, out]`` the program turns the
kernel over first, a copy of the whole kernel in EVERY call of every
program that takes it (Mistral-7B: ``q_proj``, ``k_proj`` and
``v_proj``, 100 MB a layer written and read again beside the 436 MB a
decode step has to read; PERF.md, PR 39).

So a serving session holds those kernels turned, once: a ``Turned``
node keeps ``[out, in]``, the same bits in the order the program reads
them, and the serving contracts (tpudl.models.generate) turn it back
INSIDE the traced program, where a transpose is a change of label that
XLA folds into the matmul. Training, scoring and ``generate()`` hand in
plain trees and keep ``nn.Dense`` and ``[in, out]``; a tree without
``Turned`` nodes passes through untouched.

Why not ``jax.experimental.layout`` (leave the parameters' layouts to
the compiler, ``Layout.AUTO``, and put the arrays as it answers): it
works, and is how ``tpudl.serve.weights.asked_layouts`` ASKS, in tests
and tools; but an array in another layout can only be the RESULT of a
program, the TPU runtime labels the results of an executable it has
read back from the persistent compile cache with their declared layout
whatever layout the program wrote (a warm run's weights came back
turned over and labelled as given: wrong logits), and compiling that
program anew in every process costs a warm set-up 0.9 s of its 10
(PERF.md, PR 39). A logical transpose has none of it: every array keeps
the default layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: The attention kernels the chip's compiler reads ``in``-minor, by the
#: name of their projection: what it answers, asked, for every decoder
#: configuration of the benchmark at decode and at each prefill length
#: (``scripts/weight_copies.py``; tests/test_tpu_compile.py holds this
#: list to that answer). Grouped-query ``q_proj`` / ``k_proj`` /
#: ``v_proj``; the latent family's query (``q_proj``, or ``q_b_proj``
#: after a low-rank ``q_a_proj``) and its up-projection ``kv_b_proj``.
#: ``o_proj``, the MLPs, the experts, the embedding and the head are
#: read where they lie.
TURNED = frozenset({"q_proj", "k_proj", "v_proj", "q_b_proj", "kv_b_proj"})


@jax.tree_util.register_pytree_with_keys_class
class Turned:
    """A kernel declared ``[in, out]``, held ``[out, in]``."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("value"), self.value),), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(children[0])


def _is_turned(node) -> bool:
    return isinstance(node, Turned)


def _read_in_minor(path, leaf) -> bool:
    """Whether the leaf at ``path`` is a kernel of ``TURNED``: a matrix
    under an attention module, ``<projection>/kernel`` or the
    projection's own parameter."""
    names = [getattr(key, "key", None) for key in path]
    if len(leaf.shape) != 2 or not any(
        isinstance(name, str) and name.startswith("attention")
        for name in names
    ):
        return False
    return (names[-2] if names[-1] == "kernel" else names[-1]) in TURNED


@jax.jit
def tpudl_turn(kernel):
    return kernel.T


def _turned(leaf) -> Turned:
    if isinstance(leaf, jax.ShapeDtypeStruct):
        # A compile rehearsal's shapes are turned as shapes.
        return Turned(jax.ShapeDtypeStruct(
            leaf.shape[::-1], leaf.dtype, sharding=leaf.sharding
        ))
    return Turned(tpudl_turn(leaf))


def turned_nodes(params) -> dict:
    """``{path: Turned}`` of a tree's turned kernels, the path as
    ``jax.tree_util.keystr`` writes the declared kernel's."""
    return {
        jax.tree_util.keystr(path): node
        for path, node in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=_is_turned)[0]
        if _is_turned(node)
    }


def turn(params):
    """``params`` with the kernels of ``TURNED`` under ``Turned`` nodes,
    and how many leaves and bytes that is: ``(tree, leaves, nbytes)``.
    Every other leaf is the caller's own array."""
    tree = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            _turned(leaf) if _read_in_minor(path, leaf) else leaf
        ),
        params,
    )
    kernels = [node.value for node in turned_nodes(tree).values()]
    return tree, len(kernels), sum(
        k.size * jnp.dtype(k.dtype).itemsize for k in kernels
    )


def as_declared(params):
    """``params`` as the model declares it: each ``Turned`` kernel
    ``[in, out]`` again. Inside a traced program, where it costs
    nothing; a tree without such nodes comes back as it is."""
    return jax.tree.map(
        lambda node: node.value.T if _is_turned(node) else node,
        params, is_leaf=_is_turned,
    )
