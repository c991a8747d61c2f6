"""GOOD fixture: device-kind comparisons through the canonical
normalizer (or an explicit lowering pipeline)."""
from incubator_mxnet_tpu.context import normalize_device_kind


def lookup(entry, device):
    if normalize_device_kind(entry["device_kind"]) == "tpu v4":
        return True
    if device.device_kind.lower() in ("tpu v4", "tpu v5e"):
        return True
    # comparing two raw kinds against each other is symmetric-safe
    return entry["device_kind"] == entry["other_device_kind"]
