"""One module per kind of deployment (a configuration's ``kind``): how
to make its data from the seed, how to drive the system under test and
the lower-precision control, and how to compare what they returned with
the reference."""
