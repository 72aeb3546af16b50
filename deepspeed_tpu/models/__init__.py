from . import gpt, partitioning  # noqa: F401


def cache_family(config):
    """The model family of a config: the ``gpt_inference.Family`` the
    engine, the batcher and speculative decoding drive a model through.  A
    config class names its family's module (``cache_family``), and that
    module's ``FAMILY`` is all that is taken from it; a config that names
    none is the dense GPT family's.  The one place a config picks its
    family."""
    named = getattr(config, "cache_family", None)
    if named is None:
        from .gpt_inference import DENSE
        return DENSE
    import importlib
    return importlib.import_module(f"{__name__}.{named}").FAMILY
