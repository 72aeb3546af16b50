from . import gpt, partitioning  # noqa: F401


def cache_family(config):
    """The KV-cache family module for a model config: the seven names
    (``init_cache``, ``prefill``, ``extend``, ``decode_step``,
    ``write_slot``, ``read_slot``, ``reset_slot``) the engine, the batcher
    and speculative decoding drive a model through.  The one place a
    config picks its family."""
    from .gpt_moe import GPTMoEConfig
    if isinstance(config, GPTMoEConfig):
        from . import gpt_moe_inference
        return gpt_moe_inference
    from . import gpt_inference
    return gpt_inference
