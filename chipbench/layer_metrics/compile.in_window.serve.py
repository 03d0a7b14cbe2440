"""XLA compiles inside a serving cell's window (should be 0)."""
from lib.readers import compiles_in_window as read  # noqa: F401
