"""Glue from a configuration file to the program under test, one module
per ``system`` a configuration names: it builds the program's model and
lays the benchmark's weights out as the program's parameters."""
