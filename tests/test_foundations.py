import pytest

from wukong_tpu import types
from wukong_tpu.config import GlobalConfig
from wukong_tpu.utils.errors import ErrorCode, WukongError, assert_ec


def test_id_space_split():
    assert types.PREDICATE_ID == 0
    assert types.TYPE_ID == 1
    assert types.NORMAL_ID_START == 1 << 17
    assert types.is_idx_id(5)
    assert not types.is_idx_id(1 << 17)
    assert types.is_var(-3)
    assert not types.is_var(7)


def test_dirs():
    assert types.IN == 0 and types.OUT == 1
    assert types.reverse_dir(types.IN) == types.OUT
    assert types.reverse_dir(types.OUT) == types.IN


def test_config_parse_and_immutability():
    cfg = GlobalConfig()
    cfg.finalize()
    cfg.load_str("global_num_engines 16\nglobal_mt_threshold 64\n# comment\n")
    assert cfg.num_engines == 16
    assert cfg.mt_threshold == 16  # clamped to num_engines
    cfg.load_str("global_silent off", runtime=True)
    assert cfg.silent is False
    with pytest.raises(ValueError):
        cfg.load_str("global_num_engines 2", runtime=True)
    with pytest.raises(KeyError):
        cfg.set("no_such_key", "1")


def test_error_codes():
    with pytest.raises(WukongError) as e:
        assert_ec(False, ErrorCode.VERTEX_INVALID, "col missing")
    assert e.value.code == ErrorCode.VERTEX_INVALID


@pytest.mark.parametrize("env_dir", ["/some/dir", None])
def test_compile_cache_dir_is_placed_from_outside(monkeypatch, env_dir):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and the
    code sets no directory, whatever the knob says; unset, the directory is
    the fixed <repo>/.cache/xla."""
    import os

    import jax

    from wukong_tpu.config import Global
    from wukong_tpu.utils import compilecache
    from wukong_tpu.utils.paths import REPO

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(compilecache, "_logged_dir", None)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        monkeypatch.setattr(Global, "xla_cache_dir", "/from/the/knob")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compilecache.setup_persistent_cache()
    if env_dir:
        assert got == env_dir
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert got == os.path.join(REPO, ".cache", "xla")
        assert updates["jax_compilation_cache_dir"] == got
