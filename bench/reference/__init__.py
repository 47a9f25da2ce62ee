"""Plain references, the lower-precision control and the comparison that decides ``correct``."""
