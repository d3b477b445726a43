"""The order a step takes where its launch cannot go ahead of the commit
(`PagedInferenceServer._launch_waits`: drafts, a constrained row, a
hand-off): commit n, patch from the ledger, launch n+1. `waits(srv)`
makes every plan of `srv` answer a reason, so that order runs over
traffic that would otherwise launch ahead: the reference the exactness
tests hold the launch-ahead order to. A test's patch, not an option."""


def waits(srv, on=True):
    """`srv`, every launch of which waits for the commit before it;
    `srv` as it is where `on` is false (a parametrised caller's)."""
    if on:
        launch_waits = srv._launch_waits
        srv._launch_waits = (
            lambda plan, infl: launch_waits(plan, infl) or "test")
    return srv
