"""Tests of the benchmark itself (``pytest bench/``): they import the
benchmark's modules and the program from this checkout."""
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
