"""The benchmark's yardstick: everything here is a copy or is new, and
imports nothing from the program except where a function says so."""
