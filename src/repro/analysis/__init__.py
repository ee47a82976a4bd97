from repro.analysis.hlo import collective_bytes_from_hlo
from repro.analysis.roofline import RooflineTerms

__all__ = ["collective_bytes_from_hlo", "RooflineTerms"]
