"""Runtime feature detection (reference ``python/mxnet/runtime.py:22-44``
backed by ``src/libinfo.cc``). Features reflect what this build supports."""
from __future__ import annotations

from typing import Dict

import jax


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


class Features(dict):
    """dict of name -> Feature (parity with mx.runtime.Features)."""

    def __init__(self):
        platforms = {d.platform for d in jax.devices()}
        feats = {
            "TPU": any(p not in ("cpu",) for p in platforms),
            "CPU": True,
            "CUDA": False,
            "CUDNN": False,
            "XLA": True,
            "PALLAS": True,
            "BLAS_OPEN": True,
            "F16C": True,
            "BF16": True,
            "INT64_TENSOR_SIZE": True,
            "DIST_KVSTORE": True,
            "SIGNAL_HANDLER": True,
            "PROFILER": True,
            "AMP": True,
            "ONNX": True,           # contrib.onnx export/import
            "INT8_QUANTIZATION": True,  # contrib.quantization PTQ
            "SYMBOLIC": True,       # mx.sym + Executor
            "C_API": True,          # src/c_api -> libmxtpu_capi.so
            "EXTENSION_LIBRARY": True,  # include/mxtpu_ext.h + mx.library
            "SHARDED_CHECKPOINT": True,  # mx.checkpoint (orbax)
            "KV_CACHE_GENERATION": True,  # model_zoo.generation
            "TENSORRT": False,
            "MKLDNN": False,
            "OPENCV": False,
        }
        super().__init__({k: Feature(k, v) for k, v in feats.items()})

    def is_enabled(self, name: str) -> bool:
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)


def feature_list():
    return list(Features().values())
