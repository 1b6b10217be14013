"""An architecture is a file the harness finds by the name a configuration
gives it, as ``observe.load_reader`` finds a metric's reader.

``benchmark/configs/<name>.json`` states ``"architecture": "<arch>"``;
``benchmark/architectures/<arch>.py`` holds everything the benchmark knows
of that architecture's mathematics and exports five names:

* ``scores(frames, seed, model, precision="float32", block_rows=256)``:
  the plain reference, float32 with every product at ``highest``,
  importing nothing of the program; one float32 array of span scores per
  frame. ``model`` is the configuration's whole ``model_config`` mapping;
  ``block_rows`` is how many packed rows it scores at a time (what
  ``reference.score_rows`` takes; the tests give a small one so that a
  frame crosses blocks).
* ``flops_by_part(model, piece_lengths) -> dict[str, float]``: operations
  the architecture needs for traces cut into pieces of these lengths, by
  part, real spans only; the sum is what ``step_mfu`` divides.
* ``PARTS``: scope name the program writes into the device trace -> the
  part it folds into (a key of ``flops_by_part``'s result).
* ``CONTROL``: the precision ``--control`` puts in the program's place,
  below float32. The shared ``reference._matmul`` computes
  ``reference.PRECISIONS`` (``fp8`` beside float32); an architecture whose
  control is another brings that product in its own file.
  ``tests/test_reference.py`` holds ``scores`` to computing it.
* its equations in the docstring, with each departure from the published
  description.

What every architecture shares (featurizer, layout, flax's key derivation
and initializers, the embedder and head steps, the float8 product) is in
``benchmark/reference.py``, for the file to import.
"""

from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
EXPORTS = ("scores", "flops_by_part", "PARTS", "CONTROL")


class NotFound(LookupError):
    """No such architecture; the message names the path looked for."""


def load_file(path: str, prefix: str = "benchmark_architecture_",
              ) -> ModuleType:
    """The module in the file at ``path``, imported by its path."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(name: str, rehearsal: bool = False) -> ModuleType:
    """The module of architecture ``name``: ``architectures/<name>.py``.
    A rehearsal alone may give a file's path from the root of the
    checkout instead (it holds a ``/``), for a test's double; a path that
    leads out of the checkout is refused."""
    if rehearsal and "/" in str(name):
        path, root = os.path.join(ROOT, name), os.path.realpath(ROOT)
        if os.path.commonpath([os.path.realpath(path), root]) != root:
            raise NotFound(f"{name!r} leads out of the checkout: a "
                           f"rehearsal's architecture is a file under "
                           f"{ROOT}")
    elif isinstance(name, str) and NAME.match(name):
        path = os.path.join(HERE, name + ".py")
    else:
        raise NotFound(f"{name!r} is no architecture's name (at most 64 of "
                       f"letters, digits, '_', '.' and '-')")
    if not os.path.isfile(path):
        raise NotFound(f"architecture {name!r} has no file: looked for "
                       f"{path}")
    mod = load_file(path)
    missing = [n for n in EXPORTS if not hasattr(mod, n)]
    if missing or not (mod.__doc__ or "").strip():
        raise NotFound(f"{path} lacks {', '.join(missing) or 'a docstring'}"
                       f": an architecture exports {', '.join(EXPORTS)} "
                       f"and states its equations in its docstring")
    return mod
