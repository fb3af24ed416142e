from . import (cohere2_moe, deepseek_v2, evabyte, falcon,  # noqa: F401
               kimi_linear, llama, mellum, minicpm_sala, mpt, nemotron_h, opt,
               phi4flash, solar_open2, starcoder)
from .base import MODEL_REGISTRY, ServeModelConfig, build_model

__all__ = ["MODEL_REGISTRY", "ServeModelConfig", "build_model"]
