"""One module per model family, ``families/<family>.py``, found by the
``family`` key of a configuration file. Each gives ``program_config``,
``layout``, ``decode_step`` and ``prefill`` (see ``families/dense.py``);
the family's plain reference is ``reference/<family>.py``."""
import importlib


def of(cfg: dict):
    return importlib.import_module(f"families.{cfg['family']}")
