"""ExecutorConfig: the validated construction surface for ModelExecutor.

Covers field validation, the single-point num_pages resolution (the
constructor and ``build_stack`` previously each re-derived the slot-
geometry default), and the post-deprecation removal of the old
bare-kwarg construction (now a ``TypeError`` with the migration path)."""
import pytest

from repro.serving.executors import ExecutorConfig, ModelExecutor


def _cfg():
    from repro.configs import get_reduced
    return get_reduced("chatglm3-6b")


# ---------------- validation -------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(max_slots=0),
    dict(max_len=0),
    dict(page_size=0),
    dict(num_pages=0),
    dict(num_pages=-4),
    dict(attn_impl="pallas"),
])
def test_invalid_fields_rejected_at_construction(bad):
    with pytest.raises(ValueError):
        ExecutorConfig(**bad)


def test_unknown_field_rejected():
    with pytest.raises(TypeError):
        ExecutorConfig(pages=8)


# ---------------- resolution -------------------------------------------------

def test_resolved_fills_slot_geometry_default():
    cfg = ExecutorConfig(max_slots=4, max_len=128, page_size=16)
    assert cfg.num_pages is None
    r = cfg.resolved()
    assert r.num_pages == 4 * 128 // 16 == cfg.default_num_pages
    # idempotent, and an explicit override is left alone
    assert r.resolved() is r
    assert ExecutorConfig(num_pages=7).resolved().num_pages == 7


def test_executor_allocator_sized_by_resolved_config():
    ex = ModelExecutor(_cfg(), ExecutorConfig(max_slots=2, max_len=64))
    assert ex.capacity_pages == ex.config.num_pages == 2 * 64 // 16
    assert ex.config.num_pages is not None   # executor holds the resolved cfg


def test_build_stack_and_executor_agree_without_explicit_kv_pages():
    """The dedup guarantee: with kv_pages unset, the engine's KV capacity
    comes from the same ExecutorConfig.resolved() call that sized the
    executor's stores — agreement by construction, not by parallel
    derivation."""
    from repro.launch.serve import build_stack
    executor, _, engine_cfg, _, _ = build_stack("chatglm3-6b", "real")
    assert engine_cfg.kv_pages == executor.capacity_pages
    assert engine_cfg.kv_pages == executor.config.num_pages


# ---------------- bare-kwargs removal (post-deprecation) ---------------------

def test_bare_kwargs_removed_raises_with_migration_path():
    """The PR 7 one-release deprecation window is over: bare-kwargs
    construction now fails loudly, and the message spells out the
    ExecutorConfig call to write instead."""
    with pytest.raises(TypeError, match=r"ExecutorConfig\(.*max_slots"):
        ModelExecutor(_cfg(), max_slots=2, max_len=64, num_pages=24)


def test_config_path_emits_no_deprecation_warning():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ModelExecutor(_cfg(), ExecutorConfig(max_slots=2, max_len=64))


def test_config_and_kwargs_together_rejected():
    with pytest.raises(TypeError, match="keyword arguments"):
        ModelExecutor(_cfg(), ExecutorConfig(max_slots=2, max_len=64),
                      max_slots=4)


# ---------------- device residency -------------------------------------------

def test_params_resident_in_compute_dtype():
    """Weights are cast once at construction (forward's per-call cast of
    f32 masters is then a no-op), holding the same values it produced."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.models.params import init_params
    cfg = _cfg()
    ex = ModelExecutor(cfg, ExecutorConfig(max_slots=2, max_len=64))
    masters = init_params(T.model_decls(cfg), jax.random.PRNGKey(0))
    for a, m in zip(jax.tree.leaves(ex.params), jax.tree.leaves(masters)):
        assert a.dtype == cfg.dtype == jnp.bfloat16
        assert (a == m.astype(cfg.dtype)).all()


def test_stores_refused_when_they_exceed_device_memory(monkeypatch):
    """Paged stores plus params are checked against the device's memory
    where the backend reports it; over the limit is an error naming both
    sizes, never a silent cap."""
    from repro.serving import executors
    ex = ModelExecutor(_cfg(), ExecutorConfig(max_slots=2, max_len=64))
    monkeypatch.setattr(executors, "device_bytes_limit", lambda: 1 << 20)
    with pytest.raises(ValueError, match=r"paged stores .* plus params .* "
                                         r"exceed the device's 0\.00 GiB"):
        ex._make_stores()
    monkeypatch.setattr(executors, "device_bytes_limit", lambda: 1 << 40)
    assert ex._make_stores()


def test_no_silent_legacy_fallback_on_tpu(monkeypatch):
    """On a TPU backend an arch without a paged path must ask for the
    legacy executor by name; off the TPU it still falls back."""
    import jax

    from repro.configs import get_reduced
    cfg = get_reduced("xlstm-125m")
    geometry = dict(max_slots=1, max_len=32)
    assert ModelExecutor(cfg, ExecutorConfig(**geometry)).legacy
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="no paged-KV path"):
        ModelExecutor(cfg, ExecutorConfig(**geometry))


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """Entry points keep JAX's compile cache where
    JAX_COMPILATION_CACHE_DIR says, and otherwise in one fixed directory
    of the checkout."""
    import jax

    from repro.launch.serve import REPO_ROOT, use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO_ROOT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        jax.config.update("jax_compilation_cache_dir", want)
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert (REPO_ROOT / "chip_smoke.py").is_file()
