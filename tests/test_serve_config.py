"""ServeConfig validation + the KernelMode enum + the legacy-kwarg shim."""
import jax
import numpy as np
import pytest

from repro.configs.base import DasConfig, ModelConfig, TernaryConfig
from repro.kernels.ops import KERNEL_MODES, KernelMode
from repro.models import model as MD
from repro.models.ternary_linear import tlin_apply, tlin_init
from repro.models.transformer import Runtime
from repro.serve import Request, ServeConfig, ServeEngine

CFG = ModelConfig(
    name="tiny-cfg", family="dense", n_layers=1, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
    ternary=TernaryConfig(das=DasConfig(16, 8)),
    dtype="float32", remat=False, scan_layers=False,
)


@pytest.fixture(scope="module")
def sparams():
    params = MD.init_params(jax.random.PRNGKey(0), CFG)
    return MD.export_serving(params, CFG)


# -------------------------------------------------------------------------
# KernelMode
# -------------------------------------------------------------------------

def test_kernel_mode_parse_members_and_aliases():
    assert KernelMode.parse("ref") is KernelMode.REF
    assert KernelMode.parse(KernelMode.TUNED) is KernelMode.TUNED
    # aliases map onto canonical modes
    assert KernelMode.parse("reference") is KernelMode.REF
    assert KernelMode.parse("xla") is KernelMode.REF
    assert KernelMode.parse("interp") is KernelMode.INTERPRET
    assert KernelMode.parse("mosaic") is KernelMode.PALLAS
    assert KernelMode.parse("autotune") is KernelMode.TUNED
    # the enum doubles as its string (str mixin)
    assert KernelMode.COMPILED == "compiled"
    assert str(KernelMode.AUTO) == "auto"
    assert KERNEL_MODES == ("ref", "interpret", "pallas", "compiled",
                            "tuned", "auto", "sharded")
    assert KernelMode.parse("spmd") is KernelMode.SHARDED
    assert KernelMode.parse("gspmd") is KernelMode.SHARDED


def test_kernel_mode_unknown_lists_valid_modes():
    with pytest.raises(ValueError) as ei:
        KernelMode.parse("warp9")
    msg = str(ei.value)
    for m in KERNEL_MODES:
        assert m in msg


def test_tlin_apply_accepts_aliases_rejects_junk(rng):
    p = tlin_init(jax.random.PRNGKey(1), 64, 64, np.float32)
    x = np.asarray(rng.standard_normal((2, 64)), np.float32)
    a = tlin_apply(p, x, CFG.ternary, kernel_mode="ref")
    b = tlin_apply(p, x, CFG.ternary, kernel_mode="reference")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="kernel mode"):
        tlin_apply(p, x, CFG.ternary, kernel_mode="warp9")


# -------------------------------------------------------------------------
# ServeConfig
# -------------------------------------------------------------------------

def test_serve_config_defaults_and_validation():
    sc = ServeConfig()
    assert sc.max_slots == 4 and sc.layout == "auto" and sc.policy == \
        "continuous"
    assert sc.pages_per_seq == 0 and sc.resolved_num_pages() == 0
    pc = ServeConfig(max_slots=2, max_len=64, layout="paged", page_size=16)
    assert pc.pages_per_seq == 4
    assert pc.resolved_num_pages() == 2 * 4 + 1     # worst case + null page
    with pytest.raises(ValueError):
        ServeConfig(max_slots=0)
    with pytest.raises(ValueError):
        ServeConfig(policy="banana")
    with pytest.raises(ValueError):
        ServeConfig(layout="banana")
    with pytest.raises(ValueError):                  # max_len % page_size
        ServeConfig(layout="paged", max_len=50, page_size=16)
    with pytest.raises(ValueError):                  # num_pages = 1
        ServeConfig(layout="paged", max_len=64, page_size=16, num_pages=1)
    with pytest.raises(ValueError):                  # bad kernel mode
        ServeConfig(kernel_mode="warp9")
    assert ServeConfig(kernel_mode="reference").kernel_mode == "ref"


def test_serve_config_with_updates():
    sc = ServeConfig().with_updates(max_slots=8, top_k=5)
    assert sc.max_slots == 8 and sc.top_k == 5
    with pytest.raises(TypeError, match="unknown"):
        ServeConfig().with_updates(max_slotz=8)


# -------------------------------------------------------------------------
# the legacy-kwarg shim on ServeEngine
# -------------------------------------------------------------------------

def _run_one(eng):
    eng.submit(Request(uid=0,
                       prompt=np.arange(7, dtype=np.int32) % 256,
                       max_new_tokens=5, temperature=0.7, arrival=0))
    return eng.run()[0].tokens


def test_legacy_kwargs_warn_and_match_config(sparams):
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy = ServeEngine(CFG, sparams, Runtime(), max_slots=1, max_len=32)
    assert legacy.config == ServeConfig(max_slots=1, max_len=32)
    modern = ServeEngine(CFG, sparams, Runtime(),
                         config=ServeConfig(max_slots=1, max_len=32))
    np.testing.assert_array_equal(_run_one(legacy), _run_one(modern))


def test_unknown_engine_kwarg_is_typeerror(sparams):
    with pytest.raises(TypeError, match="unknown ServeEngine kwarg"):
        ServeEngine(CFG, sparams, Runtime(), max_slotz=1)


# -------------------------------------------------------------------------
# persistent compilation cache of the entry points
# -------------------------------------------------------------------------

@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, monkeypatch, from_env):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the fixed <repo>/.jax_cache on every call."""
    import pathlib

    from repro.launch.serve import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            repo = pathlib.Path(__file__).resolve().parents[1]
            want = str(repo / ".jax_cache")
            assert enable_compile_cache() == want
            assert enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
