"""Bits one worker uploads in one compressed round, as the program's ledger
books them (its ``payload_bits``, checked against the wire's own count in
every run by ``bits_gap``)."""


def read(ctx):
    return ctx.session.uplink_bits()
