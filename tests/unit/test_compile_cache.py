"""``utils/compile_cache.py``: the persistent compile cache is placed from
outside by ``JAX_COMPILATION_CACHE_DIR``, else at one fixed path inside the
checkout. Each case runs in a child: the helper configures the process it
runs in, and this one should keep compiling as the other tests expect."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HELPER = os.path.join(REPO, "deepspeed_tpu", "utils", "compile_cache.py")

# the helper is loaded by its file (it needs jax, not the whole package),
# through a path RELATIVE to the child's cwd: what it resolves must not be
_PROBE = (
    "import importlib.util, json, sys\n"
    "spec = importlib.util.spec_from_file_location('cc', sys.argv[1])\n"
    "cc = importlib.util.module_from_spec(spec)\n"
    "spec.loader.exec_module(cc)\n"
    "before = cc.compile_cache_dir()\n"
    "print(json.dumps([before, cc.configure_compile_cache(),\n"
    "                  cc.compile_cache_dir()]))\n")


def _probe(cwd, cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **cache_env)
    r = subprocess.run(
        [sys.executable, "-c", _PROBE, os.path.relpath(HELPER, cwd)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_var_places_the_cache_and_code_sets_nothing(tmp_path):
    outside = str(tmp_path / "cache")
    before, returned, after = _probe(
        str(tmp_path), {"JAX_COMPILATION_CACHE_DIR": outside})
    # JAX read the variable itself; the helper reported it and left the
    # config exactly as it found it
    assert before == outside and returned == outside and after == outside


def test_unset_resolves_to_one_in_checkout_path_from_any_cwd(tmp_path):
    fixed = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, str(tmp_path)):
        before, returned, after = _probe(cwd, {})
        assert before is None
        assert returned == fixed and after == fixed
