"""How unevenly the grid's shards keep the chips busy in the window.

The program lays a launch's grid out across every device it sees
(``core/simulator._shard_grid``) as one program, so each chip scans its
own lanes for the same padded number of steps.  The spread is (max -
min) / mean of the devices' busy seconds over the traced window: 0 where
every chip works as long as the others.  Under that lockstep program it
reads timer noise, whatever real work each shard holds: it catches a
chip that stalls or runs a longer program than the others, not an uneven
split of real requests among lanes of one shape.  None with fewer than
two devices, or none busy.
"""


def read(ctx):
    busy = list(ctx["trace"]["busy_by_device"].values())
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return (max(busy) - min(busy)) / (sum(busy) / len(busy))
