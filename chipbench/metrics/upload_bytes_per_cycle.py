"""Bytes uploaded to the device per cycle, from the program's counters
``DeviceShardView.total_upload_bytes`` summed over the series' views."""


def read(raw):
    return raw["upload_bytes"] / raw["cycles"] if raw.get("cycles") else None
