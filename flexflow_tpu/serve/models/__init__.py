from . import (evabyte, falcon, llama, minicpm_sala, mpt,  # noqa: F401
               nemotron_h, opt, phi4flash, starcoder)
from .base import MODEL_REGISTRY, ServeModelConfig, build_model

__all__ = ["MODEL_REGISTRY", "ServeModelConfig", "build_model"]
