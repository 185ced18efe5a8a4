"""Tests of the chip benchmark; they run on the CPU and never touch a TPU."""
