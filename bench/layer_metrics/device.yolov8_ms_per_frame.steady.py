"""Device time of the yolov8 segment executables in the traced tail, summed
over chips, per yolov8 frame completed inside it."""
from bench.stats import model_device_ms_per_frame


def read(rec):
    return model_device_ms_per_frame(rec, "yolov8")
