"""idle_share.closed -- the device.

One minus the union of the device's op intervals over the traced window,
in percent.  Moves ``gen_tokens_per_s``.
"""


def read(run):
    return run.idle_share_pct()
