"""Error classes (ref python/mxnet/error.py).

The reference maps C++-side error type strings to Python exception
classes via ``register_error``; here the native layer raises through the
ctypes FFI with the same convention: a message leading with
``SomeError:`` resolves to the registered class (``distill_error``).
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["MXNetError", "InternalError", "register", "register_error",
           "distill_error"]

_ERROR_TYPES: dict = {}


def register_error(name_or_cls=None, cls=None):
    """Register an error class under its type name (ref base.py
    register_error).  Three forms: ``@register_error`` decorator,
    ``register_error("Name", Cls)``, and the decorator factory
    ``@register_error("Name")``."""
    if isinstance(name_or_cls, str):
        name = name_or_cls
        if cls is not None:
            _ERROR_TYPES[name] = cls
            return cls

        def do_register_named(k):
            _ERROR_TYPES[name] = k
            return k

        return do_register_named

    def do_register(k):
        _ERROR_TYPES[k.__name__] = k
        return k

    return do_register(name_or_cls) if name_or_cls is not None \
        else do_register


register = register_error


@register_error
class InternalError(MXNetError):
    """Internal error in the system (ref error.py:31)."""

    def __init__(self, msg):
        if "MXNet hint:" not in msg:
            msg += ("\nMXNet hint: You hit an internal error; please "
                    "report it with the stack trace.")
        super().__init__(msg)


# the reference defines each known type as BOTH an MXNetError and the
# matching builtin (python/mxnet/error.py `class ValueError(MXNetError)`),
# so `except MXNetError` still catches typed native errors AND
# `except ValueError` works — dual inheritance gives exactly that
for _builtin in (ValueError, TypeError, AttributeError, IndexError,
                 NotImplementedError, IOError, FloatingPointError,
                 RuntimeError, KeyError):
    _typed = type(_builtin.__name__, (MXNetError, _builtin), {
        "__module__": __name__,
        "__doc__": f"{_builtin.__name__} raised from the native layer "
                   "(also an MXNetError).",
        # KeyError.__str__ repr-quotes the message; plain rendering wins
        "__str__": Exception.__str__,
    })
    register_error(_builtin.__name__, _typed)
    globals()[_builtin.__name__] = _typed
    __all__.append(_builtin.__name__)


def distill_error(msg: str) -> Exception:
    """Build the registered exception for a ``Type: detail`` message
    (ref base.py c_str handling): unknown types fall back to MXNetError."""
    head, _, detail = msg.partition(":")
    head = head.strip()
    if head in _ERROR_TYPES:
        return _ERROR_TYPES[head](detail.strip() or msg)
    return MXNetError(msg)
