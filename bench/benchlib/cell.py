"""The vision family's program helpers under their older name.

``bench/families/vision.py`` drives the program; this module re-exports
what code outside the benchmark reads from it (``tests/test_tpu_compile.py``
compiles :func:`abstract_plan` for a described chip)."""

from benchlib import spec

_vision = spec.load_family("vision")

vision_config = _vision.vision_config
backend = _vision.backend
abstract_plan = _vision.abstract_plan
