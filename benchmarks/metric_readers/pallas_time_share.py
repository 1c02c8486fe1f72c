"""Device time in Pallas (Mosaic custom call) kernels over device busy time."""


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * run.trace["pallas_s"] / run.trace["busy_s"]
