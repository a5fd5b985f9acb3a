"""Traffic: the generator and the mix files it reads."""
