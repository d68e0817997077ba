"""Shared fixtures. NOTE: no XLA_FLAGS here -- smoke tests and benches see
the single real CPU device; only launch/dryrun.py forces 512 devices (and
``tools/ci.sh shard-smoke`` forces 8 for the sharded round engine).

The persistent XLA compilation cache is enabled for every test run, by the
same helper the entry points use (``repro.launch.compile_cache``): the
federated integration tests dominate tier-1 wall time and their programs
are identical across runs, so warm-cache runs skip most of the compile
cost. Override the location with ``JAX_COMPILATION_CACHE_DIR``; set it
empty to disable."""
import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()


# markers (incl. the ``slow`` tier deselected by ``tools/ci.sh smoke``)
# are registered in pytest.ini under --strict-markers; a typo'd marker is
# a collection error, not a silently-ignored tag.


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


def small_batch(cfg, key, batch=2, seq=32):
    """A valid training batch for any architecture config."""
    import jax.numpy as jnp
    if cfg.frontend.kind == "audio":
        return {"embeds": jax.random.normal(key, (batch, seq,
                                                  cfg.frontend.embed_dim)),
                "targets": jnp.zeros((batch, seq), jnp.int32)}
    if cfg.frontend.kind == "vision":
        p = cfg.frontend.tokens_per_item
        b = {"embeds": jax.random.normal(key, (batch, p,
                                               cfg.frontend.embed_dim)),
             "tokens": jax.random.randint(key, (batch, seq - p), 0,
                                          cfg.vocab_size),
             "targets": jnp.zeros((batch, seq), jnp.int32)}
        return b
    return {"tokens": jax.random.randint(key, (batch, seq), 0,
                                         cfg.vocab_size),
            "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                          (batch, seq), 0, cfg.vocab_size)}
