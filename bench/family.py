"""A model family's plug-in: ``bench/families/<family>.py``, found by the
``family`` key of a model entry in a configuration file and loaded by its
path, as ``run.reader`` loads a metric's reader.

A family module holds everything family-specific that the harness needs:

* ``model_config(name, spec)``: the program's ``ModelConfig``
  (``fleet.model_config``);
* ``reference_logits(spec, params, reqs, seq, reads, wfn=None)``: the plain
  float32 reference's logits of each sampled request, in blocks of the
  family's choosing that fit the chip (``check.reference_logits``);
* the work counts ``token_flops``, ``prefill_flops``, ``weight_bytes``,
  ``decode_state_bytes`` and ``prefill_state_bytes``, with the signatures
  of the functions of ``work.py`` that dispatch to them;
* optionally ``leaf(path, key, shape, dtype, tied)``: the rule for a weight
  leaf whose name ``fleet._leaf`` does not know (None where it has none).

A configuration of a new family joins the benchmark as a new module here,
its reference and its configuration file; no file of the harness changes.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

FAMILY_DIR = Path(__file__).resolve().parent / "families"


def of(spec: dict):
    """The family module of one model entry of a configuration file."""
    path = FAMILY_DIR / f"{spec['family']}.py"
    if not path.is_file():
        raise ValueError(f"unknown model family {spec['family']!r}: no {path}")
    return _load(str(path))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        f"family_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
