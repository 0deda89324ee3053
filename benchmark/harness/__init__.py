"""The benchmark's harness: everything here is the yardstick, not the program."""
