from . import evabyte, falcon, llama, mpt, opt, phi4flash, starcoder  # noqa: F401
from .base import MODEL_REGISTRY, ServeModelConfig, build_model

__all__ = ["MODEL_REGISTRY", "ServeModelConfig", "build_model"]
