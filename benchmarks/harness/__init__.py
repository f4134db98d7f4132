"""The benchmark's yardstick: everything a cell's number is made from.

Nothing here imports ``bench.py``; from ``elephas_tpu`` only the drivers
and builders take the system under test.
"""
