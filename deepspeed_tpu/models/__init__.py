from . import gpt, partitioning  # noqa: F401


def cache_family(config):
    """The KV-cache family module for a model config: the names the engine,
    the batcher and speculative decoding drive a model through
    (``init_cache``, ``prefill``, ``extend``, ``decode_step``,
    ``write_slot``, ``read_slot``, ``reset_slot``, ``sweep_geometry``, and
    the uncached ``apply(params, tokens, config)`` with its
    ``logical_axes(config)``).  The one place a config picks its family."""
    named = getattr(config, "cache_family", None)
    if named is not None:   # a config class that names its family's module
        import importlib
        return importlib.import_module(f"{__name__}.{named}")
    from .gpt_moe import GPTMoEConfig
    if isinstance(config, GPTMoEConfig):
        from . import gpt_moe_inference
        return gpt_moe_inference
    from . import gpt_inference
    return gpt_inference
