"""The chip benchmark's own code: cells, traffic, timelines, traces, peaks.

Nothing here is imported by the program under test, and nothing here imports
the program except the entries under ``entries/``, which drive it.
"""
