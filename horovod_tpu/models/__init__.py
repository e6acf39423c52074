"""Model zoo namespace (docs/models.md).

Lazy submodule access: ``horovod_tpu.models.llama`` works after
``import horovod_tpu.models`` without importing every family (and its
framework deps) eagerly.
"""

_FAMILIES = ("llama", "gpt2", "bert", "vit", "resnet", "moe", "dlrm",
             "mnist", "convert", "qwen3_next", "olmo_hybrid", "gated_delta",
             "nemotron_h", "mamba2", "ouro", "jamba", "mamba", "laguna",
             "latent_attention", "joyai")

__all__ = list(_FAMILIES)


def __getattr__(name):
    if name in _FAMILIES:
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod          # cache for next access
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_FAMILIES))
