#!/usr/bin/env python
"""Diagnose the runtime environment (reference ``tools/diagnose.py``):
platform, python, key package versions, framework features, device
backend reachability — the first thing to ask a bug reporter to run.

    python tools/diagnose.py [--timeout 30]
"""
from __future__ import annotations

import argparse
import os
import platform
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    print("------------Pip Info-----------")
    try:
        import pip

        print("Version      :", pip.__version__)
    except ImportError:
        print("No corresponding pip install for current python.")


def check_packages():
    print("---------Package Info----------")
    for name in ("jax", "jaxlib", "numpy", "torch", "optax", "orbax",
                 "flax"):
        try:
            mod = __import__(name)
            print(f"{name:<13}:", getattr(mod, "__version__", "unknown"))
        except ImportError:
            print(f"{name:<13}: not installed")


def check_mxnet_tpu(timeout_s):
    print("----------MXNet-TPU Info-----------")
    import mxnet_tpu as mx

    print("Version      :", mx.__version__)
    print("Directory    :", os.path.dirname(mx.__file__))
    print("Native libs  :", mx.libinfo.find_lib_path() or "not built")
    # Features() queries jax.devices(), which can HANG on a wedged
    # backend — probe in a child like the device check
    code = ("import mxnet_tpu as mx; print(mx.runtime.Features())")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout_s)
        out = proc.stdout.strip().splitlines()
        print("Features     :", out[-1] if out else proc.stderr[-200:])
    except subprocess.TimeoutExpired:
        print("Features     : (device backend unreachable)")


def check_hardware():
    print("----------Hardware Info----------")
    print("Machine      :", platform.machine())
    print("Platform     :", platform.platform())
    if sys.platform.startswith("linux"):
        try:
            out = subprocess.run(["lscpu"], capture_output=True, text=True,
                                 timeout=10).stdout
            for line in out.splitlines():
                if any(k in line for k in ("Model name", "CPU(s):",
                                           "Thread(s)", "Socket")):
                    print(line.strip())
        except Exception:
            pass


def check_devices(timeout_s):
    """Backend init can HANG (a wedged TPU runtime) — probe in a child."""
    print("----------Device Backend----------")
    code = ("import jax; ds = jax.devices(); "
            "print([f'{d.platform}:{d.device_kind}' for d in ds])")
    t0 = time.time()
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout_s)
        out = proc.stdout.strip().splitlines()
        print("Devices      :", out[-1] if out else proc.stderr[-200:])
        print(f"Init time    : {time.time() - t0:.1f} s")
    except subprocess.TimeoutExpired:
        print(f"Devices      : BACKEND UNREACHABLE (hung > {timeout_s}s — "
              "TPU runtime wedged?)")


def check_environment():
    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "JAX_", "XLA_", "DMLC_", "LD_", "OMP_")):
            print(f"{k}={v}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=int, default=30,
                    help="device-probe timeout, seconds")
    args = ap.parse_args()
    check_python()
    check_pip()
    check_packages()
    check_mxnet_tpu(args.timeout)
    check_hardware()
    check_devices(args.timeout)
    check_environment()


if __name__ == "__main__":
    main()
