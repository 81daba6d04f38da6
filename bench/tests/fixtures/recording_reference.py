"""A reference module that a configuration names: ``reference.py`` as it
is, recording each call the benchmark makes of it in ``CALLS``."""
from __future__ import annotations

import reference

CALLS = []
IMPLEMENTS = reference.IMPLEMENTS


class Pipeline(reference.Pipeline):
    def __init__(self, cfg: dict):
        CALLS.append("Pipeline")
        super().__init__(cfg)


def operands(fn):
    CALLS.append("operands")
    return reference.operands(fn)
