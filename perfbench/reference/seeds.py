"""Shared by the references: the key a seed stands for, and a
configuration as something ``jax.jit`` can take as a static argument."""

import jax


def seed_key(seed: int):
    """Seeds run past 2**31; the high bits are folded in, not lost."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32"), seed >> 31
    )


def frozen(settings: dict) -> tuple:
    """The scalar settings of a configuration, hashable."""
    return tuple(sorted(
        (k, v) for k, v in settings.items() if isinstance(v, (int, float, str))
    ))
