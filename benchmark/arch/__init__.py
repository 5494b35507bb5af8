"""What a configuration of another architecture than the dense decoder
brings: its seeded weights, its plain reference and its required work, one
package an architecture, named by the configuration's `arch`."""
