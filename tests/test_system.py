"""End-to-end behaviour of the reproduced system (the paper's full story):

user writes map+reduce only -> optimizer derives the combiner -> combine
flow replaces the reduce flow -> same answer, fewer intermediates -> the
same CombinerSpec drives the training substrate.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MapReduce, MapReduceApp
from repro.roofline import hlo_parser


class WordCount(MapReduceApp):
    key_space = 512
    value_aval = jax.ShapeDtypeStruct((), jnp.int32)
    emit_capacity = 8
    max_values_per_key = 1024

    def map(self, window, emit):
        emit(window, jnp.ones_like(window))

    def reduce(self, key, values, count):
        return jnp.sum(values)


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache lands in one fixed, git-ignored path
    inside the checkout, unless JAX_COMPILATION_CACHE_DIR names one."""
    from pathlib import Path

    from repro import compile_cache

    set_dirs = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: set_dirs.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert set_dirs == []  # JAX reads the variable itself

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert set_dirs == [("jax_compilation_cache_dir", want)]
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()


def test_paper_story_end_to_end():
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 512, (128, 8)).astype(np.int32))
    want = np.bincount(np.asarray(toks).reshape(-1), minlength=512)

    # 1. the optimizer derives a combiner from unmodified user code
    mr_opt = MapReduce(WordCount(), flow="auto")
    assert mr_opt.plan.optimized
    d = mr_opt.plan.derivation
    assert d.strategy == "monoid" and d.validated

    # 2. both flows agree (the transformation is semantics-preserving)
    res_opt = mr_opt.run(toks)
    res_base = MapReduce(WordCount(), flow="reduce").run(toks)
    np.testing.assert_array_equal(np.asarray(res_opt.values), want)
    mask = want > 0
    np.testing.assert_array_equal(
        np.asarray(res_base.values)[mask], want[mask])

    # 3. the combine flow moves fewer bytes through memory (Figs 8/9)
    def flow_bytes(mr):
        c = mr.lower(toks).compile()
        return hlo_parser.analyze_text(c.as_text()).bytes_accessed

    assert flow_bytes(mr_opt) < flow_bytes(MapReduce(WordCount(),
                                                     flow="reduce"))

    # 4. the same machinery trains a model (combiner grad accumulation)
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.training.train_step import (TrainConfig, init_train_state,
                                           make_train_step)

    cfg = get_config("llama3-8b").reduced()
    model = get_model(cfg)
    step = jax.jit(make_train_step(
        model, TrainConfig(num_microbatches=2, vocab_chunk=64,
                           warmup_steps=1, total_steps=20)))
    state = init_train_state(model, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                          cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                          cfg.vocab_size)}
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
