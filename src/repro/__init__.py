"""Extreme-classification training and serving (KDD 2020, Alibaba)."""
