"""Share of the device's busy time that the splash attention kernels (``splash_mha*``)
take, over the traced window and all devices."""

from benchmark.trace import reduce


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    kernel = sum(reduce.kernel_seconds(d["ops"], "splash_mha") for d in trace["devices"].values())
    busy = sum(reduce.busy_seconds(d["ops"]) for d in trace["devices"].values())
    return 100.0 * kernel / busy if kernel > 0 and busy > 0 else None
