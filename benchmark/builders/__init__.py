"""Builders: one file for each kind of system under test, found by the
``builder`` key of a configuration file."""
