from . import (evabyte, falcon, llama, minicpm_sala, mpt, opt,  # noqa: F401
               phi4flash, starcoder)
from .base import MODEL_REGISTRY, ServeModelConfig, build_model

__all__ = ["MODEL_REGISTRY", "ServeModelConfig", "build_model"]
