"""Which directory the persistent compilation cache resolves to."""

from pathlib import Path

from repro.launch import compile_cache
from repro.launch.compile_cache import CHECKOUT_ROOT, ENV_VAR, compile_cache_dir


def test_environment_directory_wins():
    assert compile_cache_dir({ENV_VAR: "/var/cache/jax"}) == "/var/cache/jax"


def test_default_is_fixed_path_in_checkout():
    path = Path(compile_cache_dir({}))
    assert path.name == ".jax_cache"
    assert path.parent == CHECKOUT_ROOT
    # the checkout root is the one holding this source tree
    assert Path(compile_cache.__file__).resolve().is_relative_to(CHECKOUT_ROOT / "src")
    # fixed: the same path on every call, nothing derived from time or pid
    assert compile_cache_dir({}) == compile_cache_dir({ENV_VAR: ""})
