"""Direct-run bootstrap shared by the python-guide examples.

Makes ``python examples/python-guide/<script>.py`` work from a source
checkout with no install: puts the repo root on ``sys.path`` and pins the
CPU backend (these are tiny demo datasets; set ``LGBM_GUIDE_BACKEND=tpu``
to opt into an accelerator).  Under pytest this is a no-op repeat of what
``tests/conftest.py`` already did.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

if os.environ.get("LGBM_GUIDE_BACKEND", "cpu") == "cpu":
    # must be set before the example imports jax (through lightgbm_tpu)
    os.environ["JAX_PLATFORMS"] = "cpu"
