"""The benchmark's harness: loader, traffic, driver, checks, reductions."""
