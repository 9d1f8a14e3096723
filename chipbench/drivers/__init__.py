"""Drivers: one per kind of cell, named by a traffic file."""
