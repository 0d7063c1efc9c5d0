"""The benchmark's own code: traffic, references, trace reduction, peaks.

Nothing here is imported by the program, and only ``server_main`` (the
launcher child that holds the chip) imports the program.
"""
