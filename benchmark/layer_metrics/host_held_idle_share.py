"""Percent of the traced slice, between the device's first and last operation
in it, that the device stood idle in gaps of at least 50 us NOT filed under
``fetch``: idle while the host was preparing, delivering, gathering, retrieving
or outside every span, that is: held by the host. ``lib/host_stages.py`` cuts
each gap at the edges of the program's spans and files each piece under the
innermost span that covers it, and its ``host_stages`` line gives the seconds by span, ``no span`` among them.
Never null in a traced run: 0.0 where there is no such gap (and in a capture
with no host plane or no device operation, as a rehearsal's may be)."""

from benchmark.lib import host_stages


def read(ctx):
    reduced = host_stages.of(ctx)
    return reduced and reduced["host_held_idle_share"]
