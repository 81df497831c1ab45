"""``repro.launch.cache.enable_compile_cache``: where entry points put JAX's
persistent compilation cache. Each case runs in a fresh interpreter, so this
process's JAX config is never touched."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

BODY = textwrap.dedent(f"""
    import json, sys
    sys.path.insert(0, {ROOT + "/src"!r})
    import jax
    import repro.core.creator  # importing the library sets nothing
    before = jax.config.jax_compilation_cache_dir
    from repro.launch.cache import enable_compile_cache
    got = enable_compile_cache()
    print(json.dumps({{"before": before, "returned": got,
                       "after": jax.config.jax_compilation_cache_dir}}))
""")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", BODY], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_goes_where_the_environment_says(tmp_path):
    where = str(tmp_path / "jax-cache")
    out = _run(where)
    assert out == {"before": where, "returned": where, "after": where}


def test_cache_defaults_to_a_fixed_path_in_the_checkout():
    out = _run(None)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert out == {"before": None, "returned": fixed, "after": fixed}
    assert out == _run(None)                 # same path every process
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()
