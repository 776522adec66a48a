"""Tokens generated over the whole window, per second and per chip.  The
window runs from the first due time to the last finish, so it holds all
the work and all the time, the drain included."""

from bench.stats import tokens_out


def read(rec):
    if rec.window_s <= 0:
        return None
    return tokens_out(rec) / rec.window_s / rec.chips
