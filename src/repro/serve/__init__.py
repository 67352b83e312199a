# The extraction service is the light half of this package (needs only
# repro.core); the LM engine pulls the full model stack, so it loads
# lazily — `repro.serve.engine` still works as an attribute and
# `from repro.serve.engine import ...` as a module path.
import importlib

from .extraction import CacheStats, PlanCache, ServiceResult  # noqa: F401

# sharded pulls distributed.sharding (jax) — lazy keeps the light half light
_LAZY = ("engine", "kv_cache", "sharded")


def __getattr__(name: str):
    if name in _LAZY:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
