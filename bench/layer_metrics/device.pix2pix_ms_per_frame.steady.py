"""Device time of the pix2pix segment executables in the traced tail, summed
over chips, per pix2pix frame completed inside it."""
from bench.stats import model_device_ms_per_frame


def read(rec):
    return model_device_ms_per_frame(rec, "pix2pix")
