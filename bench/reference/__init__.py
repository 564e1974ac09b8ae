"""Plain references the benchmark compares the program with.  They import
nothing of the program and take nothing it made: their inputs are made
from the seed by the benchmark itself, or read back from what the timed
path produced."""


def prng_key(seed: int, *data: int):
    """The JAX key of a benchmark seed (any whole number up to 64 bits),
    folded with ``data``."""
    import jax
    seed = int(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                           (seed >> 32) & 0xFFFFFFFF)
    for d in data:
        k = jax.random.fold_in(k, d)
    return k
