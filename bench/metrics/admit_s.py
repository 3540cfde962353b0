"""Seconds from the factor on the device to the bank's resident stacks
ready: the gather, the casts and phase 1 (host clock around
block_until_ready)."""


def read(ctx):
    return ctx.admit_s
