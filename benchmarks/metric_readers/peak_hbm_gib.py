"""peak_bytes_in_use of the fullest device, in GiB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
