"""The step programs of ``serve/step.py`` under the names the per-layer
metrics find them by in the device trace."""

from __future__ import annotations

import re

from lib.measure import DECODE_PROGRAMS, PREFILL_PROGRAMS


def _module_name(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def test_step_program_names_pinned():
    """Each bucketed step program of ``serve/step.py`` lowers to a module
    whose name the readers' patterns find, in its own group: renaming a
    step function fails here instead of silencing the metrics that read
    it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    from repro.models.model import ModelConfig
    from repro.serve import step as S
    from repro.serve.engine import ServeEngine
    from repro.serve.engine.cache_manager import paged_gather_jit

    cfg = ModelConfig(name="tiny-names", family="dense", num_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                      d_ff=64, vocab=128, dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ps, budget, n = 4, 32, 4
    eng = ServeEngine(cfg, params, n_slots=n, budget=budget, paged=True,
                      page_size=ps)
    cache = eng.cache_mgr.cache
    i32 = jnp.int32
    prefill = S._cached_prefill_bucket(cfg, None, 8)
    one = prefill(params, jnp.zeros((1, 8), i32), i32(5))[1]
    prefix = paged_gather_jit(
        cfg, cache, {k: jnp.zeros((1,), i32)
                     for k in eng.cache_mgr.table_ids(0)})
    lowered = {
        "prefill": prefill.lower(params, jnp.zeros((1, 8), i32), i32(5)),
        "prefill_ext": S._cached_prefill_ext_bucket(cfg, None, ps, 8).lower(
            params, jnp.zeros((1, 8), i32), prefix, i32(ps), i32(ps + 3)),
        "align": S._cached_align_bucket(cfg, 8, budget, ps, 0).lower(
            one, i32(5), i32(0)),
        "decode_packed": S._cached_decode_packed(cfg, None).lower(
            params, cache, jnp.zeros((2, 1), i32), jnp.full((2,), -1, i32),
            jnp.asarray(np.full((2,), n, np.int32))),
        "decode_full": S._cached_decode(cfg, None).lower(
            params, cache, jnp.zeros((n, 1), i32), jnp.full((n,), -1, i32)),
    }
    names = {k: _module_name(v) for k, v in lowered.items()}
    for kind, name in names.items():
        decode = kind.startswith("decode")
        assert bool(re.search(DECODE_PROGRAMS, name)) is decode, name
        assert bool(re.search(PREFILL_PROGRAMS, name)) is not decode, name
