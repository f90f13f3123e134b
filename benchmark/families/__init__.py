"""Checkpoint families: one file each, chosen by a configuration's ``family``."""

import importlib


def load_family(config: dict):
    return importlib.import_module(f"benchmark.families.{config['family']}")
