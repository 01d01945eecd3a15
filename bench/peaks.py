"""The chip's published peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``.  A kind that is not in the table is an
    error: a roofline or an MFU against a guessed peak means nothing."""
    table = json.loads(Path(path).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        known = ", ".join(sorted(table["devices"]))
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path} (known: {known})") from None
