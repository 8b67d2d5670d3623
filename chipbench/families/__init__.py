"""Model families, one file each, found by a configuration file's
``model_type``: ``families/<model_type>.py``.

A family file owns everything about its architecture that the harness
needs, and gives:

- ``check(c)``: refuse a file that cannot run as it states.  ``c``
  holds the configuration's own keys, without those every family shares
  (``configmap.SHARED``); the family maps, fixes or folds each of them
  and refuses any other key or value;
- ``model_config(c)``: the program's ``ModelConfig`` for file ``c``;
- ``folds(c)``: leaf name -> factor by which the served weight exceeds
  the published one;
- ``draw(names, c)``: how the published value of the leaf whose path
  holds ``names`` is drawn, as a function ``(key, shape) -> float32
  array`` (``weights.normal``, ``weights.const``, or a rule of the
  family's own);
- ``shape(c)``: the work object the per-layer readers count with
  (``ctx["shape"]``).

Adding a family is adding its file, a configuration file that names it,
and a plain reference under ``references/``: no other file changes.
"""
