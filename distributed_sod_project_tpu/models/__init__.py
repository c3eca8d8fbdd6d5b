from .registry import build_model, kind_of, list_models, register_model

__all__ = ["build_model", "kind_of", "list_models", "register_model"]
