"""kernel.ed25519_us_per_ksig.bulk: device time of the verify kernel and of
the SHA-512 challenge (modules named in the configuration's "kernels"), in
microseconds per 1,000 signatures submitted in the traced window, so lanes
padded to the bucket count as waste."""


def read(run):
    red = run.get("trace")
    if not red or not run.get("lanes_submitted"):
        return None
    kernel_s = sum(red["kernel_s"].values())
    if kernel_s <= 0:
        return None
    return 1e6 * kernel_s / (run["lanes_submitted"] / 1000.0)
