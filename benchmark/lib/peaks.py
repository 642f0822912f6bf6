"""Published peaks of the devices the benchmark may run on, keyed by
`device_kind` as JAX reports it. A device that is not in the table is an
error, never a default."""

import json
import os

from . import ROOT

_PATH = os.path.join(ROOT, "benchmark", "peaks.json")


def table():
    with open(_PATH) as f:
        return json.load(f)


def peaks(device_kind):
    t = table()
    if device_kind not in t:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(t)}); add a row with "
            f"its source, do not guess")
    return t[device_kind]
