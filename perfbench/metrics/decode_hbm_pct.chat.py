"""Bytes the decode steps need (weights once, each active slot's K/V at
its true length) over device time times the HBM peak."""
from pbench import layers


def read(run):
    return layers.roofline_pct(run, "decode", bound="bytes")
