"""Device-0 idle share of the profiled slice of a serving cell."""
from lib.readers import idle_share as read  # noqa: F401
