"""The block a serving builder built, kept for the reference of its
configuration.

``run.py`` hands a reference the parameters, the configuration and a
sequence, and nothing of what was served.  A reference whose limits have to
be held on the served object itself (``references/ouro.py``: the residual
stream's precision, which no comparison of logits can hold through 192
layer applications) finds it here: the builder calls ``keep(config, block)``
as it returns the block, the reference ``block_of(config)`` with the same
configuration object -- the one ``run.py`` read and hands to both.
"""
_KEPT = {}      # id(config) -> (config, block); the config kept so its id stays


def keep(config, block):
    _KEPT[id(config)] = (config, block)
    return block


def block_of(config):
    """The block built from this configuration object; an error where none
    was (a reference that needs one cannot decide without it)."""
    if id(config) not in _KEPT:
        raise LookupError(
            f"no block was kept for the configuration {config.get('name')!r}: "
            "its builder has to call lib.served.keep(config, block)")
    return _KEPT[id(config)][1]
