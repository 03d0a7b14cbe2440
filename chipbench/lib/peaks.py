"""The table of peaks (``chipbench/peaks.json``), keyed by ``device_kind``."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


def peak_for(device_kind):
    with open(_PATH) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {known}); add a row "
                       "with its source, never a default")
    return row
