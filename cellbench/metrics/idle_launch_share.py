"""Device: idle time between programs, % of the traced span, while the
scheduler's thread was in `sched/launch`: the patch and dispatch of the
next program. Put down gap by gap (`hostplane.idle_shares`); the four
`idle_*_share` add up to the between-program part of
`device_idle_share`."""
from cellbench import hostplane


def read(ctx):
    return hostplane.idle_share_of(ctx, "launch")
