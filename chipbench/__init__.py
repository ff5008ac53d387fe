"""The benchmark harness for the FAIR-k training round on the chip."""
