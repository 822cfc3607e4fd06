"""Buffer-donation audit, callable on any compiled program.

PR 5's donation test proved the fused train step donates its state
(every old leaf deleted, >=80% of buffer pointers reused in place);
that check lived inside one test. This generalizes it: hand
``audit_donation`` any compiled callable plus its args, name which
positional args the program is supposed to donate, and get back the
outputs plus a report — so the fused train step and its K-step
window, and every serving program that takes the paged KV pool and
returns it (decode, speculative verify, the seat programs, the
migration scatter: the ownership rule of
``tpudl.serve.cache.PagedKVCache``, audited in
tests/test_serve_donation.py), all audit with the same ten lines.

Donation failing SILENTLY is the point: XLA falls back to copying when
a donated buffer cannot be aliased (layout mismatch, an extra
reference, a dtype change), the program stays correct, and the only
symptom is doubled memory traffic on the hot loop. The audit makes it
loud:

    out, report = audit_donation(step, (state, batch, rng),
                                 donate_argnums=(0,))
    assert report.ok, report.describe()

The pointer-reuse check compares ``unsafe_buffer_pointer`` of the
donated input shards against every output leaf's — reuse means XLA
aliased in place rather than copied. ``min_reuse`` defaults to 0.8:
scalars and tiny leaves legitimately land elsewhere.

NOTE: the audited call CONSUMES its donated args (that is what
donation means) — pass state you can afford to lose, and keep using
the returned outputs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Sequence, Tuple


class DonationError(AssertionError):
    """A program expected to donate copied instead."""


@dataclasses.dataclass
class DonationReport:
    num_leaves: int
    num_deleted: int
    reuse_frac: float
    min_reuse: float
    #: jax.tree_util key paths of donated leaves still alive after the
    #: call (donation silently fell back to copy for these).
    undeleted: List[str]

    @property
    def ok(self) -> bool:
        return not self.undeleted and self.reuse_frac >= self.min_reuse

    def describe(self) -> str:
        if self.ok:
            return (
                f"donation ok: {self.num_deleted}/{self.num_leaves} "
                f"leaves consumed, {self.reuse_frac:.0%} buffers "
                f"reused in place"
            )
        parts = []
        if self.undeleted:
            shown = ", ".join(self.undeleted[:8])
            more = (
                f" (+{len(self.undeleted) - 8} more)"
                if len(self.undeleted) > 8 else ""
            )
            parts.append(
                f"{len(self.undeleted)}/{self.num_leaves} donated "
                f"leaves were NOT consumed — XLA fell back to copying "
                f"them: {shown}{more}"
            )
        if self.reuse_frac < self.min_reuse:
            parts.append(
                f"only {self.reuse_frac:.0%} of donated buffer "
                f"pointers reappear in the outputs "
                f"(need >= {self.min_reuse:.0%}) — leaves are "
                f"silently copying"
            )
        return "donation audit failed: " + "; ".join(parts)


def buffer_pointers(tree) -> set:
    """Device buffer pointers of every addressable shard in a pytree."""
    import jax

    out = set()
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for shard in shards:
            out.add(shard.data.unsafe_buffer_pointer())
    return out


def audit_donation(
    fn,
    args: Sequence[Any],
    donate_argnums: Sequence[int] = (0,),
    min_reuse: float = 0.8,
) -> Tuple[Any, DonationReport]:
    """Run ``fn(*args)`` and report whether the args named by
    ``donate_argnums`` were actually donated (consumed + buffers
    reused in the outputs). Returns ``(outputs, report)``."""
    import jax

    donated = [args[i] for i in donate_argnums]
    labeled = [
        (jax.tree_util.keystr(path), leaf)
        for arg in donated
        for path, leaf in jax.tree_util.tree_flatten_with_path(arg)[0]
    ]
    old_ptrs = buffer_pointers(donated)
    outputs = fn(*args)
    undeleted = [
        key for key, leaf in labeled
        if hasattr(leaf, "is_deleted") and not leaf.is_deleted()
    ]
    new_ptrs = buffer_pointers(outputs)
    reuse = (
        len(old_ptrs & new_ptrs) / len(old_ptrs) if old_ptrs else 1.0
    )
    report = DonationReport(
        num_leaves=len(labeled),
        num_deleted=len(labeled) - len(undeleted),
        reuse_frac=reuse,
        min_reuse=min_reuse,
        undeleted=undeleted,
    )
    return outputs, report


def assert_donation(
    fn,
    args: Sequence[Any],
    donate_argnums: Sequence[int] = (0,),
    min_reuse: float = 0.8,
) -> Any:
    """``audit_donation`` that raises :class:`DonationError` on
    failure and returns the program outputs on success."""
    outputs, report = audit_donation(
        fn, args, donate_argnums=donate_argnums, min_reuse=min_reuse
    )
    if not report.ok:
        raise DonationError(report.describe())
    return outputs
