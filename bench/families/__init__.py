"""Model families: one module per family, ``families/<family>.py``, found
by the configuration file's ``family`` key (``weights.family``). A new
architecture is added as a file here, beside its configuration.

A family module defines:

- ``shapes(conf)``: a frozen dataclass of the sizes, read from the
  source's own keys. The common code reads ``family`` (the module's
  name), ``layers``, ``vocab``, ``tied`` and ``d``; the family's own
  functions read the rest.
- ``program_check(s)``: the ``ArchConfig`` fields (or properties) the
  program must have, as a dict; ``harness.program_config`` refuses a
  program that differs in any of them.
- ``published(s, key, dtype)``: the weights in the published layout, a
  flat dict of stacked arrays drawn from ``key`` in the served dtype;
  ``to_program(s, w)``: the program's parameter tree from them, as a
  checkpoint loader would build it.
- ``split_first``: published leaves that a sharded draw must constrain
  to the engine's sharding before ``to_program`` (a gather in it would
  otherwise draw them whole on every chip), each with its path in the
  program's tree; ``published_shardings(s, mesh)``: the sharding of each
  published leaf for the reference over a sharded engine's chips.
- ``matrices``: the published leaves the int8 control rounds, each with
  the axis its scales run along (``control.py``).
- ``logits(s, w, tokens)``: the plain reference, the published forward
  pass in float32 jax.numpy at ``Precision.HIGHEST`` over ``tokens``, a
  multiple of ``reference.Q_BLOCK`` long.
- ``prefill_flops(s, prompt_len)``, ``decode_flops(s, context)`` and
  ``decode_bytes(s, ticks, contexts, lanes_per_tick)``: the work the
  model needs, by the rules in ``flops.py``. ``lanes_per_tick`` is the
  mean number of tokens a decode tick produced, for a family whose bytes
  per tick depend on it (experts routed, recurrent state read per lane).

Modules whose name starts with ``_`` hold code that families share.
"""
