from repro.roofline.collectives import collective_bytes_from_hlo

__all__ = ["collective_bytes_from_hlo"]
