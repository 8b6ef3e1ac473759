"""Where the persistent compilation cache goes.

``JAX_COMPILATION_CACHE_DIR`` is placed from outside (the chip's machine
comes with it set) and nothing in the package may point the cache
anywhere else, whatever the ``MXNET_*`` knobs say. Without it, a bare
import arms nothing, and ``base.arm_compile_cache()`` — what
``chip_smoke.py`` and ``bench.py``'s child call — gives one fixed path
inside the checkout. Each case runs in a fresh interpreter: jax reads the
variable once, at import.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import jax
import mxnet_tpu
from mxnet_tpu import aot
from mxnet_tpu.base import arm_compile_cache
seen = {"import": jax.config.jax_compilation_cache_dir}
aot.CompileCache(sys.argv[1])
seen["aot"] = jax.config.jax_compilation_cache_dir
seen["helper_returns"] = arm_compile_cache()
seen["helper"] = jax.config.jax_compilation_cache_dir
print(json.dumps(seen))
"""


def _probe(tmp_path, cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "MXNET_COMPILE_CACHE",
                        "MXNET_TPU_AOT_CACHE", "MXNET_TPU_AOT")}
    env.update(env_over, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(tmp_path / "store")],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(cwd))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("knobs", [("MXNET_COMPILE_CACHE",),
                                   ("MXNET_TPU_AOT_CACHE",),
                                   ("MXNET_COMPILE_CACHE",
                                    "MXNET_TPU_AOT_CACHE")])
def test_jax_compilation_cache_dir_is_never_overridden(tmp_path, knobs):
    placed = str(tmp_path / "placed-from-outside")
    seen = _probe(tmp_path, tmp_path, JAX_COMPILATION_CACHE_DIR=placed,
                  **{k: str(tmp_path / k.lower()) for k in knobs})
    assert seen == {"import": placed, "aot": placed,
                    "helper_returns": placed, "helper": placed}


def test_helper_gives_one_fixed_path_inside_the_checkout(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    a = _probe(tmp_path, ROOT)
    b = _probe(tmp_path, other)
    fixed = os.path.join(ROOT, ".cache", "jax")
    assert a["helper_returns"] == b["helper_returns"] == fixed
    assert a["helper"] == b["helper"] == fixed
    # a bare import arms nothing; the AOT store arms its own xla tier
    assert a["import"] is None and b["import"] is None
    assert a["aot"] == str(tmp_path / "store" / "xla")
