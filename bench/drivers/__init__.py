"""Drivers of a measured window, one per ``driver`` a traffic mix names."""
