from . import (cohere2_moe, evabyte, falcon, llama,  # noqa: F401
               minicpm_sala, mpt, nemotron_h, opt, phi4flash, starcoder)
from .base import MODEL_REGISTRY, ServeModelConfig, build_model

__all__ = ["MODEL_REGISTRY", "ServeModelConfig", "build_model"]
