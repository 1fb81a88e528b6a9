"""paddle.inference parity — the deployment Predictor.

Reference (SURVEY.md §2.6): `AnalysisPredictor` (paddle_inference_api.h) —
load model, run the IR pass pipeline, execute with zero-copy IO handles;
`Config` carries device/optimization knobs.

TPU-native: a deployable model is serialized StableHLO (jax.export bytes,
saved by jit.save) + weights. "Analysis passes + engine selection" collapse
into one AOT XLA compile at `create_predictor` time; zero-copy IO is PJRT
device buffers held by the handle objects (donation on request).
"""
from __future__ import annotations

import os
import pickle
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor

__all__ = ["Config", "Predictor", "create_predictor", "PrecisionType",
           "PlaceType", "LLMPredictor", "init_cache", "Completion",
           "PagedServingEngine", "TokenEvent",
           "BlockManager", "RejectedError", "DeadlineExceededError",
           "ServingRouter", "FailoverMismatchError"]

from .llm import LLMPredictor, init_cache  # noqa: E402,F401
from .serving import (BlockManager, Completion,  # noqa: E402,F401
                      DeadlineExceededError, FailoverMismatchError,
                      PagedServingEngine, RejectedError, ServingRouter,
                      TokenEvent)


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    XPU = "xpu"


class Config:
    """Reference: paddle/fluid/inference/api/analysis_config.cc."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        if prog_file and not os.path.splitext(prog_file)[1]:
            # path prefix form: Config("inference/model")
            prog_file, params_file = (prog_file + ".pdmodel",
                                      prog_file + ".pdiparams")
        self.prog_file = prog_file
        self.params_file = params_file
        self._device = "tpu"
        self._device_id = 0
        self._precision = PrecisionType.Float32
        self._enable_memory_optim = True
        self._donate_inputs = False
        self._ir_optim = True

    def set_prog_file(self, path: str):
        self.prog_file = path

    def set_params_file(self, path: str):
        self.params_file = path

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._device = "gpu"
        self._device_id = device_id
        self._precision = precision

    def enable_tpu(self, device_id: int = 0,
                   precision=PrecisionType.Bfloat16):
        self._device = "tpu"
        self._device_id = device_id
        self._precision = precision

    def disable_gpu(self):
        self._device = "cpu"

    def enable_memory_optim(self, flag: bool = True):
        self._enable_memory_optim = flag

    def switch_ir_optim(self, flag: bool = True):
        # gates the pre-compile pass pipeline (the AnalysisPredictor's
        # OptimizeInferenceProgram stage); XLA's own fusion always runs
        self._ir_optim = flag

    def device(self) -> str:
        return self._device

    def precision(self):
        return self._precision


class _IOHandle:
    """Zero-copy tensor handle (reference: ZeroCopyTensor/paddle_tensor.h):
    holds the PJRT buffer; copy_from_cpu stages host→device once."""

    def __init__(self, name: str, predictor: "Predictor", is_input: bool):
        self.name = name
        self._pred = predictor
        self._is_input = is_input

    def copy_from_cpu(self, data: np.ndarray):
        self._pred._inputs[self.name] = jnp.asarray(data)

    def share_external_data(self, tensor):
        arr = tensor._data if isinstance(tensor, Tensor) else jnp.asarray(tensor)
        self._pred._inputs[self.name] = arr

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._pred._outputs[self.name])

    def to_tensor(self) -> Tensor:
        return Tensor._from_data(self._pred._outputs[self.name])

    def shape(self):
        store = (self._pred._inputs if self._is_input
                 else self._pred._outputs)
        arr = store.get(self.name)
        return list(arr.shape) if arr is not None else None


class Predictor:
    """Reference: AnalysisPredictor (analysis_predictor.cc:1738 Run,
    :1690 ZeroCopyRun)."""

    def __init__(self, config: Config):
        self.config = config
        self._inputs: Dict[str, jnp.ndarray] = {}
        self._outputs: Dict[str, jnp.ndarray] = {}
        self._load(config)

    # -- loading ---------------------------------------------------------
    def _load(self, config: Config):
        with open(config.prog_file, "rb") as f:
            payload = pickle.load(f)
        self._exported = None
        self._layer = None
        if isinstance(payload, dict) and payload.get("stablehlo_program"):
            from ..pir import Program

            # precision selection — the load-time half of the analysis
            # stage (reference: analysis_predictor.cc:1252): the
            # fold/CSE/DCE pipeline ran at SAVE, before lowering (a
            # deserialized StableHLO blob is an opaque call_exported the
            # jaxpr passes cannot see), and the save path shipped a
            # bf16-rewritten variant this Config picks
            blob = payload["stablehlo_program"]
            if (config.precision() in (PrecisionType.Bfloat16,
                                       PrecisionType.Half)
                    and getattr(config, "_ir_optim", True)
                    and payload.get("stablehlo_program_bf16")):
                blob = payload["stablehlo_program_bf16"]
            self._exported = Program.deserialize(blob)
            self._feed_names = list(self._exported.feed_names)
            self._fetch_names = list(self._exported.fetch_names)
        elif isinstance(payload, dict) and payload.get("layer") is not None:
            # class-pickle fallback (jit.save without input_spec)
            from ..jit.serialization import load as jit_load

            prefix = config.prog_file[:-len(".pdmodel")]
            self._layer = jit_load(prefix)
            self._feed_names = ["x"]
            self._fetch_names = ["out"]
        else:
            raise ValueError(
                f"{config.prog_file}: no StableHLO program and no "
                f"reconstructible layer — re-save with jit.save(input_spec=…)")

    # -- reference API ---------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def get_input_handle(self, name: str) -> _IOHandle:
        return _IOHandle(name, self, True)

    def get_output_handle(self, name: str) -> _IOHandle:
        return _IOHandle(name, self, False)

    def run(self, inputs: Optional[List] = None) -> Optional[List[Tensor]]:
        """inputs given → returns outputs (paddle's list API); otherwise
        zero-copy style: stage via handles, fetch via handles."""
        if inputs is not None:
            for name, x in zip(self._feed_names, inputs):
                self._inputs[name] = (x._data if isinstance(x, Tensor)
                                      else jnp.asarray(x))
        missing = [n for n in self._feed_names if n not in self._inputs]
        if missing:
            raise ValueError(f"inputs not set: {missing}")
        if self._exported is not None:
            outs = self._exported.run(self._inputs)
        else:
            feed = [Tensor._from_data(self._inputs[n])
                    for n in self._feed_names]
            result = self._layer(*feed)
            leaves = jax.tree.leaves(
                result, is_leaf=lambda x: isinstance(x, Tensor))
            outs = [t._data if isinstance(t, Tensor) else t for t in leaves]
        self._outputs = dict(zip(self._fetch_names, outs))
        if inputs is not None:
            return [Tensor._from_data(o) for o in outs]
        return None

    def clone(self) -> "Predictor":
        return Predictor(self.config)


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)
