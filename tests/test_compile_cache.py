"""utils.enable_compile_cache: the cache can be placed from outside, and is
otherwise ONE fixed path inside the checkout (the directory is part of the
cache key — a path that moves never hits).  Each probe is its own
interpreter: the setting is process-global."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HELPER = os.path.join(REPO, "distributed_tensorflow_tpu", "utils",
                      "compile_cache.py")
# the helper's file alone (it imports only os and jax): importing the whole
# package three times would cost this test ~15 s for nothing
PROBE = (
    "import importlib.util, jax\n"
    f"spec = importlib.util.spec_from_file_location('cc', {HELPER!r})\n"
    "cc = importlib.util.module_from_spec(spec)\n"
    "spec.loader.exec_module(cc)\n"
    "print(cc.enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def _probe(cwd, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu")
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         cwd=str(cwd), capture_output=True, text=True,
                         timeout=120, check=True).stdout.splitlines()
    returned, configured, min_secs = out[-3:]
    return returned, configured, float(min_secs)


def test_environment_variable_wins_and_no_other_path_is_set(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    returned, configured, min_secs = _probe(tmp_path, cache_env=placed)
    # JAX read the variable itself; the helper set nothing over it
    assert returned == configured == placed
    assert min_secs == 0.0          # the 1-2 s kernels are cached too


def test_default_is_one_in_checkout_path_from_any_working_directory(
        tmp_path):
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    want = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, elsewhere):
        returned, configured, _ = _probe(cwd)
        assert returned == configured == want
    assert not (elsewhere / ".jax_cache").exists()
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
