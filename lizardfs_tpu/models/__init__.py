"""Flagship end-to-end data-plane pipelines (graft entry points)."""
