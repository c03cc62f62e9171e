"""Published peaks of one chip, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peaks(device_kind: str) -> dict:
    """The row for ``device_kind``; a kind that is not in the table is an error."""
    if device_kind not in TABLE:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}")
    return TABLE[device_kind]
