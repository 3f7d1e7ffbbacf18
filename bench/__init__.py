"""Chip benchmark of the Pix2Pix + YOLOv8n serving pipeline (see ``run.py``)."""
