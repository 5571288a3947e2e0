"""How much longer the busiest chip worked than the mean of the chips (%):
100 x (max / mean - 1) of the devices' busy seconds in the traced window
(``per_device_busy_s`` of ``xplane.reduce``). Each chip runs the same program
on its shard, padded to the largest, so the rows it holds set its time."""


def read(run):
    t = run.trace
    busy = list((t or {}).get("per_device_busy_s", {}).values())
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return 100.0 * (max(busy) / (sum(busy) / len(busy)) - 1.0)
