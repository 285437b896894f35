"""Architecture configs: one module per assigned architecture.

Each module exposes ``full()`` (the exact published config) and ``reduced()``
(a small same-family config for CPU smoke tests).  ``repro.configs.get(arch)``
resolves by id; ``ARCHS`` lists every id.
"""
from importlib import import_module

ARCHS = (
    "granite-moe-1b-a400m",
    "qwen3-moe-30b-a3b",
    "yi-9b",
    "mistral-nemo-12b",
    "command-r-35b",
    "llama3-8b",
    "rwkv6-1.6b",
    "paligemma-3b",
    "whisper-tiny",
    "recurrentgemma-2b",
    "deepseek-v2-lite",
)

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCHS}


def get(arch_id: str, reduced: bool = False):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.reduced() if reduced else mod.full()
