"""The chip benchmark's harness: find a cell's parts by name, generate its
traffic, drive the program under test, reduce traces and decide `correct`."""
